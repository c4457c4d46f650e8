"""Step ledger + anomaly watchdog (ISSUE 5): wall-time attribution,
goodput/MFU accounting, incremental shipping, online anomaly verdicts,
beat-size capping, and the dmlc-top renderer."""

import json
import time

import pytest

from dmlc_tpu import telemetry
from dmlc_tpu.telemetry import StepLedger, Watchdog
from dmlc_tpu.telemetry.anomaly import ANOMALY_KINDS


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset()
    telemetry.reset_steps()
    yield
    telemetry.reset()
    telemetry.reset_steps()


# ---------------------------------------------------------------------------
# StepLedger: records, attribution, goodput/MFU
# ---------------------------------------------------------------------------

def test_step_record_decomposes_wall_time():
    led = StepLedger(peak_flops=1e9)
    led.step_begin()
    with telemetry.span("feed.wait", stage="feed"):
        time.sleep(0.02)
    with telemetry.span("collective.allreduce", stage="collective"):
        time.sleep(0.01)
    time.sleep(0.02)  # "compute"
    rec = led.step_end(tokens=1000, flops=5e6)
    assert rec["wall_s"] >= 0.05
    assert 0.015 <= rec["feed_wait_s"] <= rec["wall_s"]
    assert 0.005 <= rec["collective_s"] <= rec["wall_s"]
    # residual compute >= the bare sleep
    assert rec["compute_s"] >= 0.015
    # decomposition sums to wall exactly (compute is the residual)
    total = rec["feed_wait_s"] + rec["collective_s"] + rec["compute_s"]
    assert total == pytest.approx(rec["wall_s"], rel=1e-6)
    assert rec["goodput_tokens_per_s"] == pytest.approx(
        1000 / rec["wall_s"], rel=1e-6)
    assert rec["mfu"] == pytest.approx(5e6 / rec["wall_s"] / 1e9, rel=1e-6)


def test_step_ignores_other_threads_feed_spans():
    """Producer-side feed spans on OTHER threads must not be billed to
    the step — overlap is the feed pipeline's whole point."""
    import threading

    led = StepLedger()
    led.step_begin()

    def producer():
        with telemetry.span("feed.parse", stage="feed"):
            time.sleep(0.05)

    t = threading.Thread(target=producer)
    t.start()
    time.sleep(0.01)
    t.join()
    rec = led.step_end()
    assert rec["feed_wait_s"] == 0.0


def test_declared_flops_derive_step_flops():
    led = StepLedger(peak_flops=1e9)
    led.declare_flops_per_token(100.0)
    led.step_begin()
    rec = led.step_end(tokens=50)
    assert rec["flops"] == pytest.approx(5000.0)
    assert rec["mfu"] is not None


def test_ledger_records_step_span_in_ring():
    led = StepLedger()
    led.step_begin()
    led.step_end()
    names = [s["name"] for s in telemetry.spans()]
    assert "step" in names


def test_abandoned_step_does_not_leak_span_stack():
    led = StepLedger()
    led.step_begin()  # never ended (raising train step)
    led.step_begin()  # must unwind the dangling one
    rec = led.step_end()
    assert rec["seq"] == 1
    assert telemetry.open_spans() == []


def test_records_since_incremental_ship_contract():
    led = StepLedger()
    for _ in range(6):
        led.step_begin()
        led.step_end()
    recs, last = led.records_since(0, limit=4)
    assert [r["seq"] for r in recs] == [1, 2, 3, 4]
    assert last == 4  # truncated: cursor stops at last returned
    recs, last = led.records_since(last)
    assert [r["seq"] for r in recs] == [5, 6]
    assert last == 6
    assert led.records_since(6) == ([], 6)


def test_ledger_bounded_and_summary_keys():
    led = StepLedger(capacity=4)
    for _ in range(10):
        led.step_begin()
        led.step_end(tokens=10)
    assert len(led.records()) == 4
    s = led.summary()
    assert s["steps"] == 4
    assert s["step_time_p50"] <= s["step_time_p99"]
    assert s["goodput_tokens_per_s"] > 0
    assert "mfu" in s


def test_ledger_publishes_local_registry_families():
    led = StepLedger()
    led.step_begin()
    led.step_end(tokens=10)
    snap = telemetry.snapshot()
    assert snap["counters"]["step"]["count"] == 1
    assert "time_secs" in snap["histograms"]["step"]
    assert snap["gauges"]["step"]["goodput_tokens_per_s"] > 0


def test_bytes_fed_defaults_to_feed_counter_delta():
    led = StepLedger()
    led.step_begin()
    telemetry.inc("feed", "bytes_to_device", 4096)
    rec = led.step_end()
    assert rec["bytes_fed"] == 4096.0


def test_peak_flops_env_override(monkeypatch):
    from dmlc_tpu.telemetry import steps

    monkeypatch.setenv("DMLC_PEAK_FLOPS", "123.0")
    assert steps.detect_peak_flops() == 123.0
    monkeypatch.setenv("DMLC_PEAK_FLOPS", "garbage")
    assert steps.detect_peak_flops() is None


# ---------------------------------------------------------------------------
# heartbeat shipping: steps sub-doc + beat byte cap
# ---------------------------------------------------------------------------

class _FakeClient:
    rank = 0

    def __init__(self):
        self.payloads = []

    def send_metrics(self, payload):
        self.payloads.append(payload)


def _beat(client, **kw):
    from dmlc_tpu.telemetry.heartbeat import HeartbeatSender

    hb = HeartbeatSender(client, auto_start=False, ship_trace=True, **kw)
    hb.send_once()
    return hb, json.loads(client.payloads[-1])


def test_heartbeat_ships_step_records_incrementally():
    telemetry.step_begin()
    telemetry.step_end(tokens=5)
    c = _FakeClient()
    hb, doc = _beat(c)
    assert [r["seq"] for r in doc["trace"]["steps"]] == [1]
    assert doc["trace"]["step_seq"] == 1
    # nothing new: next beat ships no steps
    hb.send_once()
    doc2 = json.loads(c.payloads[-1])
    assert doc2["trace"]["steps"] == []
    telemetry.step_begin()
    telemetry.step_end()
    hb.send_once()
    doc3 = json.loads(c.payloads[-1])
    assert [r["seq"] for r in doc3["trace"]["steps"]] == [2]


def test_beat_byte_cap_truncates_oldest_first(monkeypatch):
    monkeypatch.setenv("DMLC_TELEMETRY_MAX_BEAT_BYTES", "20000")
    for i in range(500):  # a span storm (one name: a span's name is a
        # counter family, so it is not where the instance goes)
        with telemetry.span("storm", stage="smoke", args={"i": i}):
            pass
    for _ in range(8):
        telemetry.step_begin()
        telemetry.step_end(tokens=1)
    c = _FakeClient()
    _hb, doc = _beat(c)
    assert len(c.payloads[-1]) <= 20000
    spans = doc["trace"]["spans"]
    # truncation drops the OLDEST: the newest span must survive
    kept = [s["args"]["i"] for s in spans if s["name"] == "storm"]
    assert 499 in kept and 0 not in kept
    # the shrink is counted where /metrics can see it
    assert telemetry.counters_snapshot()["telemetry"][
        "beats_truncated"] == 1


def test_beat_under_cap_not_truncated():
    telemetry.step_begin()
    telemetry.step_end()
    c = _FakeClient()
    _hb, doc = _beat(c)
    assert doc["trace"]["steps"]
    assert "telemetry" not in telemetry.counters_snapshot()


# ---------------------------------------------------------------------------
# Watchdog verdicts
# ---------------------------------------------------------------------------

def _steps(n, wall, start=1, feed=0.0, goodput=None, t0=1000.0):
    out = []
    for i in range(n):
        out.append({"seq": start + i, "wall_s": wall,
                    "feed_wait_s": feed, "t_wall": t0 + i,
                    "goodput_tokens_per_s": goodput})
    return out


def test_watchdog_flags_straggler_rank_only():
    w = Watchdog(k=4, window=3)
    w.ingest(0, _steps(20, 0.01), anchor=1.0)
    w.ingest(1, _steps(20, 0.05), anchor=1.0)
    rep = w.report()
    assert rep["ranks"]["1"]["flags"] == ["straggler"]
    assert rep["ranks"]["0"]["flags"] == []
    assert {(a["rank"], a["kind"]) for a in rep["active"]} == {
        (1, "straggler")}
    assert rep["recent_verdicts"]
    # verdict counters + event ring + markers all fired
    assert telemetry.counters_snapshot()["anomaly"][
        "straggler_flags"] == 1
    kinds = [e["kind"] for e in telemetry.events_tail()]
    assert "anomaly" in kinds
    assert any("straggler rank 1" in m["name"]
               for m in w.trace_markers())


def test_watchdog_straggler_clears_when_rank_recovers():
    w = Watchdog(k=4, window=3)
    w.ingest(0, _steps(20, 0.01), anchor=1.0)
    w.ingest(1, _steps(20, 0.05), anchor=1.0)
    assert w.report()["ranks"]["1"]["flags"] == ["straggler"]
    w.ingest(1, _steps(20, 0.01, start=21), anchor=1.0)
    assert w.report()["ranks"]["1"]["flags"] == []


def test_watchdog_single_spike_not_flagged():
    w = Watchdog(k=4, window=3)
    w.ingest(0, _steps(20, 0.01), anchor=1.0)
    w.ingest(1, _steps(19, 0.01) + _steps(1, 0.5, start=20), anchor=1.0)
    assert w.report()["ranks"]["1"]["flags"] == []


def test_watchdog_regression_on_sustained_slowdown():
    w = Watchdog(window=3)
    w.ingest(0, _steps(30, 0.01), anchor=1.0)
    w.ingest(0, _steps(10, 0.03, start=31), anchor=1.0)
    assert "regression" in w.report()["ranks"]["0"]["flags"]


def test_watchdog_feed_stall_dominance():
    w = Watchdog(window=3)
    recs = _steps(30, 0.02, feed=0.015)
    w.ingest(0, recs, anchor=1.0)
    assert "feed_stall" in w.report()["ranks"]["0"]["flags"]


def test_watchdog_goodput_collapse():
    w = Watchdog(window=3)
    w.ingest(0, _steps(30, 0.01, goodput=1000.0), anchor=1.0)
    w.ingest(0, _steps(10, 0.01, start=31, goodput=100.0), anchor=1.0)
    assert "goodput_collapse" in w.report()["ranks"]["0"]["flags"]


def test_watchdog_dedups_reshipped_records():
    w = Watchdog(window=3)
    recs = _steps(10, 0.01)
    w.ingest(0, recs, anchor=1.0)
    w.ingest(0, recs, anchor=1.0)  # torn-beat reship
    assert w.report()["ranks"]["0"]["steps"] == 10


def test_watchdog_restart_resets_baselines():
    w = Watchdog(window=3)
    w.ingest(0, _steps(30, 0.01), anchor=1.0)
    # restarted worker: new anchor, seq restarts at 1 — records must be
    # accepted (not dropped by the old seq high-water mark)
    w.ingest(0, _steps(5, 0.02), anchor=2.0)
    assert w.report()["ranks"]["0"]["steps"] == 5


def test_watchdog_ingest_json_and_malformed_payloads():
    w = Watchdog(window=2)
    payload = json.dumps({"trace": {"anchor": 1.0,
                                    "steps": _steps(3, 0.01)}})
    w.ingest_json(0, payload)
    assert w.report()["ranks"]["0"]["steps"] == 3
    w.ingest_json(0, "not json")
    w.ingest_json(0, json.dumps({"trace": {"steps": [
        {"wall_s": "garbage"}, 17, {"seq": 9, "wall_s": 0.01,
                                    "t_wall": 1.0}]}}))
    assert w.report()["ranks"]["0"]["steps"] == 4


def test_watchdog_drop_forgets_rank():
    w = Watchdog(window=3)
    w.ingest(0, _steps(10, 0.01), anchor=1.0)
    w.drop(0)
    assert w.report()["ranks"] == {}


def test_watchdog_prometheus_gauges():
    w = Watchdog(k=4, window=3)
    w.ingest(0, _steps(20, 0.01), anchor=1.0)
    w.ingest(1, _steps(20, 0.05), anchor=1.0)
    text = w.prometheus_text()
    assert '# TYPE dmlc_anomaly_active gauge' in text
    assert 'dmlc_anomaly_active{rank="1",kind="straggler"} 1' in text
    assert 'dmlc_anomaly_active{rank="0",kind="straggler"} 0' in text
    for kind in ANOMALY_KINDS:
        assert f'kind="{kind}"' in text


# ---------------------------------------------------------------------------
# flight-recorder anomaly markers
# ---------------------------------------------------------------------------

def test_flight_trace_includes_anomaly_markers():
    from dmlc_tpu.telemetry import FlightRecorder

    fr = FlightRecorder()
    t0 = time.time()
    fr.ingest(0, {"anchor": t0, "spans": [
        {"seq": 1, "name": "work", "cat": "x", "ts": 0.0,
         "dur": 5.0, "tid": 1}]})
    fr.marker_source = lambda: [{"t": t0 + 1.0, "name": "anomaly:x"}]
    doc = fr.to_chrome_trace()
    markers = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
    assert len(markers) == 1
    assert markers[0]["name"] == "anomaly:x"
    assert markers[0]["ts"] == pytest.approx(1e6, rel=0.01)
    assert markers[0]["s"] == "g"


# ---------------------------------------------------------------------------
# dmlc-top renderer
# ---------------------------------------------------------------------------

def test_dmlc_top_render_table():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "dmlc_top", os.path.join(os.path.dirname(__file__), "..",
                                 "scripts", "dmlc_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    doc = {
        "anomalies": {
            "cluster": {"median_step_s": 0.02},
            "ranks": {
                "0": {"step_time_s": 0.02, "step_time_ewma_s": 0.021,
                      "goodput_tokens_per_s": 12000.0, "mfu": 0.41,
                      "feed_stall_frac": 0.05, "flags": []},
                "1": {"step_time_s": 0.17, "step_time_ewma_s": 0.171,
                      "goodput_tokens_per_s": 1500.0, "mfu": None,
                      "feed_stall_frac": None,
                      "flags": ["straggler"]},
            },
            "active": [{"rank": 1, "kind": "straggler"}],
            "recent_verdicts": [{"rank": 1, "kind": "straggler",
                                 "detail": "slow"}],
        },
        "healthz": {"ranks_reporting": 2,
                    "ranks": {"0": 0.1, "1": 4.2},
                    "dead_ranks": [1]},
    }
    text = top.render_table(doc, "http://t:1")
    lines = text.splitlines()
    assert "RANK" in lines[1]
    row0 = next(line for line in lines if line.strip().startswith("0 "))
    row1 = next(line for line in lines if line.strip().startswith("1 "))
    assert "41.0" in row0 and "12,000" in row0
    assert "straggler" in row1 and "DEAD" in row1
    # None fields render as "-", never crash
    assert " - " in row1 or row1.rstrip().endswith("-") or "-" in row1
    assert any("! rank 1 straggler" in line for line in lines)


def test_dmlc_top_render_empty_doc():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "top_view_fixture", os.path.join(os.path.dirname(__file__), "..",
                                         "scripts", "dmlc_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    text = top.render_table({"anomalies": {}, "healthz": {}}, "u")
    assert "RANK" in text  # header renders even with nothing to show
