"""Compute observability (telemetry.compute): compile ledger (by
phase since PR 36), XLA cost/roofline, HBM accounting (PR 16).

Everything runs on the virtual CPU mesh: the AOT compile path,
cost_analysis extraction, the host-RSS memory fallback and the storm
detector are all backend-agnostic, which is exactly the property the
profiling layer must keep (profiling can never be allowed to break the
model on ANY backend).
"""

import importlib.util
import json
import logging
import os
import time

import jax
import jax.numpy as jnp
import pytest

from dmlc_tpu import telemetry
from dmlc_tpu.base import DMLCError
from dmlc_tpu.telemetry import compute
from dmlc_tpu.telemetry.anomaly import COMPUTE_KINDS, Watchdog
from dmlc_tpu.telemetry.exporters import validate_exposition_text


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    telemetry.reset_events()
    compute.reset_compute()
    yield
    telemetry.reset()
    telemetry.reset_events()
    compute.reset_compute()


def _load_top():
    spec = importlib.util.spec_from_file_location(
        "compute_top_fixture", os.path.join(
            os.path.dirname(__file__), "..", "scripts", "dmlc_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    return top


# ---------------------------------------------------------------------------
# compile ledger: hit/trace counting + recompile attribution
# ---------------------------------------------------------------------------

def test_profiled_jit_counts_hits_and_traces():
    pj = compute.profiled_jit(lambda x: x * 2.0, site="t.basic")
    x = jnp.arange(4, dtype=jnp.float32)
    for _ in range(3):
        assert float(pj(x)[0]) == 0.0
    st = pj.stats()
    assert st["traces"] == 1 and st["hits"] == 2
    assert st["recompiles"] == 0 and st["signatures"] == 1
    assert compute.sites()["t.basic"] is pj
    assert compute.recompiles_total() == 0


def test_recompile_attributed_to_signature():
    pj = compute.profiled_jit(lambda x: x + 1, site="t.attr")
    pj(jnp.zeros((4,), jnp.float32))
    pj(jnp.zeros((8,), jnp.float32))     # new shape -> recompile
    pj(jnp.zeros((8,), jnp.int32))       # new dtype -> recompile
    st = pj.stats()
    assert st["traces"] == 3 and st["recompiles"] == 2
    # the LAST recompile is attributed to the (shape, dtype) that
    # triggered it, human-readably
    assert "8" in st["last_signature"] and "int32" in st["last_signature"]
    assert compute.recompiles_total() == 2


def test_static_args_split_signatures():
    calls = []

    def f(x, n):
        calls.append(n)
        return x * n

    pj = compute.profiled_jit(f, site="t.static", static_argnums=(1,))
    x = jnp.ones((2,), jnp.float32)
    assert float(pj(x, 2)[0]) == 2.0
    assert float(pj(x, 3)[0]) == 3.0     # same aval, new static value
    assert float(pj(x, 2)[0]) == 2.0     # cache hit on the first
    st = pj.stats()
    assert st["traces"] == 2 and st["hits"] == 1


def test_unhashable_static_falls_back_like_plain_jit():
    pj = compute.profiled_jit(lambda x, n: x, site="t.unhash",
                              static_argnums=(1,))
    with pytest.raises(Exception):  # jax's own unhashable-static error
        pj(jnp.ones((2,)), [1, 2])
    assert pj.stats()["aot_fallbacks"] >= 1


def test_lowering_error_is_raised_not_compiled_twice():
    """A program that cannot lower is the caller's error: it surfaces
    from the first (AOT) attempt, with no fallback counted and no
    second trace through plain jit."""
    traces = []

    def bad(x):
        traces.append(1)
        raise TypeError("cannot lower this")

    pj = compute.profiled_jit(bad, site="t.bad")
    with pytest.raises(TypeError, match="cannot lower"):
        pj(jnp.ones((2,)))
    assert len(traces) == 1
    assert pj.stats()["aot_fallbacks"] == 0


def test_signature_memo_does_not_pin_superseded_pytrees():
    """A training loop hands the step a fresh params dict every call;
    the identity memo may remember the latest one, never the history
    (each remembered tree would pin a copy of the weights)."""
    import gc
    import weakref

    pj = compute.profiled_jit(lambda p: p["w"] * 2, site="t.memo")
    old = {"w": jnp.ones((4,))}
    ref = weakref.ref(old["w"])  # a dict itself takes no weak reference
    pj(old)
    del old
    pj({"w": jnp.ones((4,))})
    gc.collect()
    assert ref() is None, "the memo kept a superseded argument alive"


def test_signature_cap_raises_dmlc_error():
    pj = compute.profiled_jit(lambda x: x, site="t.cap",
                              max_signatures=2)
    pj(jnp.zeros((1,), jnp.float32))
    pj(jnp.zeros((2,), jnp.float32))
    with pytest.raises(DMLCError, match="signature cap"):
        pj(jnp.zeros((3,), jnp.float32))
    # the capped site still serves its existing signatures
    assert float(pj(jnp.zeros((2,), jnp.float32))[0]) == 0.0


def test_compile_span_lands_on_flight_recorder():
    pj = compute.profiled_jit(lambda x: x * x, site="t.span")
    pj(jnp.ones((3,), jnp.float32))
    trace = json.loads(telemetry.to_chrome_trace_json())
    (ev,) = [e for e in trace["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "compute.compile"]
    assert ev["args"]["site"] == "t.span"
    assert ev["args"]["signature"] == "3:float32"
    assert ev["args"]["trace"] == 1


def _compile_spans():
    return [r for r in telemetry.spans()
            if r["name"].startswith("compute.")]


def test_compile_opens_three_children_in_order():
    """A miss at a fresh signature: ``compute.compile`` over trace,
    lower, backend (in that order, each its child), then the first
    call; the site's seconds are the spans' own."""
    pj = compute.profiled_jit(lambda x: x * x + 1.0, site="t.phases")
    pj(jnp.ones((5,), jnp.float32))
    recs = _compile_spans()
    assert [r["name"] for r in recs] == [
        "compute.compile.trace", "compute.compile.lower",
        "compute.compile.backend", "compute.compile",
        "compute.first_call"]  # the ring is in close order
    trace, lower, backend, whole, first = recs
    for child in (trace, lower, backend):
        assert child["parent"] == whole["id"]
        assert child["args"]["site"] == "t.phases"
    assert whole["parent"] is None and first["parent"] is None
    assert trace["ts"] + trace["dur"] <= lower["ts"]
    assert lower["ts"] + lower["dur"] <= backend["ts"]
    assert backend["ts"] + backend["dur"] <= whole["ts"] + whole["dur"]
    assert whole["ts"] + whole["dur"] <= first["ts"]
    assert whole["args"]["cache"] == backend["args"]["cache"]
    assert whole["args"]["cache"] in ("hit", "miss", "off")
    st = pj.stats()
    assert st["trace_secs_total"] == pytest.approx(trace["dur"] / 1e6,
                                                   abs=1e-6)
    assert st["lower_secs_total"] == pytest.approx(lower["dur"] / 1e6,
                                                   abs=1e-6)
    assert st["backend_secs_total"] == pytest.approx(
        backend["dur"] / 1e6, abs=1e-6)
    assert st["first_call_secs_total"] == pytest.approx(
        first["dur"] / 1e6, abs=1e-6)
    assert min(st["trace_secs_total"], st["lower_secs_total"],
               st["backend_secs_total"], st["first_call_secs_total"]) > 0
    assert st["compile_secs_total"] == pytest.approx(
        st["trace_secs_total"] + st["lower_secs_total"]
        + st["backend_secs_total"], abs=1e-3)
    assert st["compile_secs_total"] <= whole["dur"] / 1e6
    # the counter pairs are the spans', under the names they had
    c = telemetry.counters_snapshot()["compute"]
    assert c["compile_count"] == c["compile_trace_count"] \
        == c["compile_lower_count"] == c["compile_backend_count"] \
        == c["first_call_count"] == 1
    assert c["compile_secs"] == pytest.approx(whole["dur"] / 1e6)
    assert c["compile_backend_secs"] == pytest.approx(
        backend["dur"] / 1e6)


def test_second_call_of_a_signature_opens_no_span():
    pj = compute.profiled_jit(lambda x: x - 1.0, site="t.again")
    x = jnp.ones((4,), jnp.float32)
    pj(x)
    n = len(_compile_spans())
    first = pj.stats()
    pj(x)
    pj(x)
    assert len(_compile_spans()) == n
    again = pj.stats()
    assert again["hits"] == 2
    for field in ("compile_secs_total", "trace_secs_total",
                  "lower_secs_total", "backend_secs_total",
                  "first_call_secs_total"):
        assert again[field] == first[field], field
    # a second signature is a second compile and a second first call
    pj(jnp.ones((6,), jnp.float32))
    assert len(_compile_spans()) == 2 * n
    assert pj.stats()["first_call_secs_total"] \
        > first["first_call_secs_total"]


def test_compile_inside_an_open_span_is_its_child():
    """A deployment without warm-up pays a compile inside a request's
    span: the ledger's spans hang under whatever the thread has open."""
    pj = compute.profiled_jit(lambda x: x * 3.0, site="t.child")
    with telemetry.span("serving.prefill.run", stage="serving"):
        pj(jnp.ones((2,), jnp.float32))
    by_name = {r["name"]: r for r in telemetry.spans()}
    run = by_name["serving.prefill.run"]
    assert by_name["compute.compile"]["parent"] == run["id"]
    assert by_name["compute.first_call"]["parent"] == run["id"]
    assert by_name["compute.compile.backend"]["parent"] \
        == by_name["compute.compile"]["id"]
    assert by_name["compute.compile"]["depth"] == 1


def test_goodput_compile_bucket_fills_from_the_new_span():
    from dmlc_tpu.telemetry import goodput

    assert goodput._span_bucket("compute.compile", "compute") \
        == ("compile", goodput._PRI_SPECIFIC)
    # the children lie inside it: swept, they would count twice
    for name in ("compute.compile.trace", "compute.compile.backend",
                 "compute.first_call", "compile:t.site"):
        assert goodput._span_bucket(name, "compute") is None
    led = goodput.GoodputLedger()
    pj = compute.profiled_jit(lambda x: jnp.sin(x) @ x, site="t.goodput")
    pj(jnp.ones((8, 8), jnp.float32))
    (whole,) = [r for r in telemetry.spans()
                if r["name"] == "compute.compile"]
    doc = led.status()
    assert doc["buckets"]["compile"] == pytest.approx(
        whole["dur"] / 1e6, rel=0.05)


# ---------------------------------------------------------------------------
# the persistent cache's own word: JAX's monitoring events
# ---------------------------------------------------------------------------

@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compilation cache of this test's own, that keeps
    every program however small; JAX's process-wide cache object is
    reset around it so that it looks at the directory."""
    from jax._src import compilation_cache

    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_persistent_cache_miss_then_hit(persistent_cache):
    def fn(x):
        return jnp.tanh(x) * 2.0 + x

    x = jnp.ones((7,), jnp.float32)
    jax.block_until_ready(x)  # its own small program, before we count
    before = telemetry.counters_snapshot().get("compute", {})
    cold = compute.profiled_jit(fn, site="t.cache")
    cold(x)
    st = cold.stats()
    assert (st["cache_misses"], st["cache_hits"]) == (1, 0)
    assert any(persistent_cache.iterdir())  # the entry was written
    jax.clear_caches()
    warm = compute.profiled_jit(fn, site="t.cache")  # a fresh wrapper
    warm(x)
    st = warm.stats()
    assert (st["cache_misses"], st["cache_hits"]) == (0, 1)
    words = [r["args"]["cache"] for r in telemetry.spans()
             if r["name"] == "compute.compile"]
    assert words == ["miss", "hit"]
    after = telemetry.counters_snapshot()["compute"]
    assert after["cache_misses"] - before.get("cache_misses", 0) == 1
    assert after["cache_hits"] - before.get("cache_hits", 0) == 1
    assert after["cache_retrieval_secs"] > before.get(
        "cache_retrieval_secs", 0)


def test_cache_events_reach_the_site_compiling_on_this_thread(
        monkeypatch):
    """The listener driven with JAX's own ``record_event``: an event
    during a site's backend compile is that site's, one outside any is
    the process's alone (a plain ``jax.jit`` site)."""
    from jax import monitoring

    pj = compute.profiled_jit(lambda x: x + 2.0, site="t.events")
    real = type(pj._jit.trace(jnp.ones(3)).lower()).compile

    def compile_and_report(lowered, *a, **kw):
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        return real(lowered, *a, **kw)

    lowered_type = type(pj._jit.trace(jnp.ones(3)).lower())
    monkeypatch.setattr(lowered_type, "compile", compile_and_report)
    telemetry.reset()
    pj(jnp.ones((3,), jnp.float32))
    monkeypatch.undo()
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/core/some_other_event")
    st = pj.stats()
    assert (st["cache_hits"], st["cache_misses"]) == (1, 0)
    (whole,) = [r for r in telemetry.spans()
                if r["name"] == "compute.compile"]
    assert whole["args"]["cache"] == "hit"
    c = telemetry.counters_snapshot()["compute"]
    assert c["cache_hits"] == 1 and c["cache_misses"] == 1
    assert c["cache_retrieval_secs"] == pytest.approx(0.25)


def test_reregister_survives_reset():
    pj = compute.profiled_jit(lambda x: x, site="t.rereg")
    pj(jnp.zeros((2,), jnp.float32))
    compute.reset_compute()
    assert compute.sites() == {}
    pj.reregister()   # what the engine's process-wide program cache does
    assert compute.sites()["t.rereg"] is pj
    assert pj.stats()["traces"] == 1  # ledger state rode along


# ---------------------------------------------------------------------------
# XLA cost extraction + roofline verdicts
# ---------------------------------------------------------------------------

def test_cost_extraction_on_cpu():
    pj = compute.profiled_jit(lambda a, b: a @ b, site="t.cost")
    a = jnp.ones((16, 16), jnp.float32)
    pj(a, a)
    cost = pj.stats()["last_cost"]
    assert cost is not None
    # a 16x16x16 matmul is ~2*16^3 = 8192 flops; XLA may fuse a bit
    # around it but the figure must be in that ballpark, not zero
    assert cost["flops"] >= 4096
    assert cost["bytes_accessed"] > 0


def test_roofline_both_verdicts():
    # intensity 100 flops/byte against balance 10 -> compute-bound
    r = compute.roofline(flops=1e6, bytes_accessed=1e4, wall_s=1.0,
                         peak_flops=1e7, peak_bw=1e6)
    assert r["bound"] == "compute"
    assert r["mfu"] == pytest.approx(0.1)
    # intensity 0.1 against the same balance -> memory-bound
    r = compute.roofline(flops=1e3, bytes_accessed=1e4, wall_s=1.0,
                         peak_flops=1e7, peak_bw=1e6)
    assert r["bound"] == "memory"
    assert r["membw_util"] == pytest.approx(0.01)
    assert r["intensity"] == pytest.approx(0.1)


def test_roofline_degrades_to_none():
    r = compute.roofline(None, None, 1.0, None, None)
    assert r["bound"] is None and r["mfu"] is None
    r = compute.roofline(1e6, 1e4, 0.0, 1e7, 1e6)  # bad wall
    assert r["bound"] is None


def test_step_ledger_carries_membw_and_bound(monkeypatch):
    monkeypatch.setenv("DMLC_PEAK_FLOPS", "1e9")
    monkeypatch.setenv("DMLC_PEAK_HBM_GBPS", "1")  # 1e9 B/s, balance=1
    telemetry.reset_steps()
    telemetry.step_begin()
    time.sleep(0.001)
    telemetry.step_end(tokens=128, flops=1e5, bytes_accessed=1e7)
    summ = telemetry.ledger().summary()
    assert summ["bound"] == "memory"       # intensity 0.01 < balance 1
    assert summ["membw_util"] is not None and summ["membw_util"] > 0
    roof = telemetry.ledger().roofline_summary()
    assert roof["bound"] == "memory"
    assert roof["peak_flops"] == pytest.approx(1e9)
    assert roof["peak_membw_bytes_per_s"] == pytest.approx(1e9)


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

def test_sample_hbm_reports_peak_and_gauges():
    doc = compute.sample_hbm()
    assert doc["source"] in ("device", "host_rss")
    assert doc["peak_bytes"] and doc["peak_bytes"] > 0
    snap = telemetry.export_json()
    assert snap["gauges"]["compute"]["hbm_peak_bytes"] > 0


def test_sample_hbm_host_rss_fallback(monkeypatch):
    # a backend whose devices report no memory_stats: the sample must
    # degrade to the host-RSS proxy, flagged as such, never go dark
    monkeypatch.setattr(jax, "local_devices",
                        lambda: (_ for _ in ()).throw(RuntimeError("x")))
    doc = compute.sample_hbm(publish=False)
    assert doc["source"] == "host_rss" and not doc["available"]
    assert doc["peak_bytes"] and doc["peak_bytes"] > 0
    assert doc["limit_bytes"] and doc["limit_bytes"] > doc["peak_bytes"]
    assert doc["headroom_bytes"] is not None


# ---------------------------------------------------------------------------
# views: status / report / prometheus text
# ---------------------------------------------------------------------------

def test_status_empty_without_sites():
    assert compute.status() == {}


def test_status_and_report_schema():
    pj = compute.profiled_jit(lambda x: x + 1, site="t.schema")
    pj(jnp.zeros((2,), jnp.float32))
    pj(jnp.zeros((4,), jnp.float32))
    compute.sample_hbm()
    st = compute.status()
    assert st["traces"] == 2 and st["recompiles"] == 1
    assert "storm" in st and st["hbm_peak_bytes"] > 0
    rep = compute.report()
    assert rep["enabled"] and "t.schema" in rep["sites"]
    assert rep["traces_total"] == 2
    assert rep["recompiles_total"] == 1
    assert rep["storm"]["threshold"] >= 1
    assert rep["hbm"]["peak_bytes"] > 0
    assert "phases" not in rep
    site = rep["sites"]["t.schema"]
    assert site["compile_secs_total"] == pytest.approx(
        site["trace_secs_total"] + site["lower_secs_total"]
        + site["backend_secs_total"], abs=1e-3)
    assert {"first_call_secs_total", "cache_hits", "cache_misses"} \
        <= set(site)
    assert "bound" in rep["roofline"]


def test_storm_detector_trips_on_churn(monkeypatch):
    monkeypatch.setenv("DMLC_COMPUTE_STORM_TRACES", "3")
    pj = compute.profiled_jit(lambda x: x, site="t.storm")
    for n in range(1, 5):
        pj(jnp.zeros((n,), jnp.float32))
    storm = compute.status()["storm"]
    assert storm["active"]
    assert storm["sites"][0]["site"] == "t.storm"
    assert storm["sites"][0]["traces_in_window"] == 4


def test_prometheus_text_per_site_families():
    pj = compute.profiled_jit(lambda x: x, site="t.prom")
    pj(jnp.zeros((2,), jnp.float32))
    pj(jnp.zeros((3,), jnp.float32))
    text = compute.prometheus_text()
    assert '# TYPE dmlc_compute_recompiles_total counter' in text
    assert 'dmlc_compute_recompiles_total{site="t.prom"} 1' in text
    assert 'dmlc_compute_traces_total{site="t.prom"} 2' in text
    assert 'dmlc_compute_cache_hits_total{site="t.prom"} 0' in text
    # the seconds by phase and the persistent cache's answers, through
    # the same per-site families
    for name in ("dmlc_compute_site_compile_secs_total",
                 "dmlc_compute_site_trace_secs_total",
                 "dmlc_compute_site_lower_secs_total",
                 "dmlc_compute_site_backend_secs_total",
                 "dmlc_compute_site_first_call_secs_total"):
        assert f"# TYPE {name} counter" in text
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith(name + '{site="t.prom"}')]
        assert float(line.split()[-1]) > 0
    assert 'dmlc_compute_site_persistent_cache_hits_total{site="t.prom"}' \
        in text
    validate_exposition_text(text)


# ---------------------------------------------------------------------------
# dark-cheap contract: DMLC_COMPUTE_PROFILE=0
# ---------------------------------------------------------------------------

def test_disabled_returns_plain_jit(monkeypatch):
    monkeypatch.setenv("DMLC_COMPUTE_PROFILE", "0")
    pj = compute.profiled_jit(lambda x: x * 2.0, site="t.off",
                              static_argnums=())
    assert not hasattr(pj, "stats")  # the plain jax.jit object
    assert float(pj(jnp.ones((2,), jnp.float32))[0]) == 2.0
    assert compute.sites() == {}     # no registry entry
    assert compute.status() == {}


def test_disabled_registers_no_listener_and_no_span(monkeypatch):
    from jax._src import monitoring

    monkeypatch.setenv("DMLC_COMPUTE_PROFILE", "0")
    # as in a process that never made a site
    monkeypatch.setattr(compute, "_listening", False)
    monkeypatch.setattr(
        monitoring, "_event_listeners",
        [f for f in monitoring._event_listeners
         if f is not compute._on_cache_event])
    monkeypatch.setattr(
        monitoring, "_event_duration_secs_listeners",
        [f for f in monitoring._event_duration_secs_listeners
         if f is not compute._on_cache_duration])
    pj = compute.profiled_jit(lambda x: x * 5.0, site="t.dark")
    assert float(pj(jnp.ones((2,), jnp.float32))[0]) == 5.0
    assert compute._listening is False
    assert compute._on_cache_event not in monitoring.get_event_listeners()
    assert compute._on_cache_duration \
        not in monitoring.get_event_duration_listeners()
    assert not [r for r in telemetry.spans()
                if r["name"].startswith("compute.")]
    assert "compute" not in telemetry.counters_snapshot()
    # ... and with the profile on, the first site registers both, once
    monkeypatch.setenv("DMLC_COMPUTE_PROFILE", "1")
    compute.profiled_jit(lambda x: x, site="t.lit")
    compute.profiled_jit(lambda x: x, site="t.lit2")
    assert compute._listening is True
    assert monitoring.get_event_listeners().count(
        compute._on_cache_event) == 1
    assert monitoring.get_event_duration_listeners().count(
        compute._on_cache_duration) == 1


# ---------------------------------------------------------------------------
# watchdog integration + dmlc-top pane
# ---------------------------------------------------------------------------

def _storm_status_doc(active=True):
    return {"traces": 6, "hits": 0, "recompiles": 5,
            "hbm_peak_bytes": 1 << 30,
            "storm": {"active": active, "window_s": 60.0, "threshold": 4,
                      "sites": [{"site": "smoke.churn",
                                 "traces_in_window": 6}]}}


def test_watchdog_ingest_compute_flags_and_clears():
    w = Watchdog(log=logging.getLogger("t"))
    w.ingest_json(1, json.dumps({"compute": _storm_status_doc()}))
    rep = w.report()
    assert rep["ranks"]["1"]["flags"] == ["recompile_storm"]
    assert rep["ranks"]["1"]["compute"]["recompiles"] == 5
    assert rep["ranks"]["1"]["compute"]["storm_sites"] == ["smoke.churn"]
    assert "recompile_storm" in COMPUTE_KINDS
    creport = w.compute_report()
    assert creport["storming_ranks"] == [1]
    assert creport["ranks"]["1"]["traces"] == 6
    # the worker's window slides past the churn: the flag clears
    w.ingest_compute(1, _storm_status_doc(active=False))
    assert w.report()["ranks"]["1"]["flags"] == []
    assert w.compute_report()["storming_ranks"] == []


def test_watchdog_ingest_compute_sanitizes():
    w = Watchdog(log=logging.getLogger("t"))
    w.ingest_compute(1, {"traces": "NaN-ish", "recompiles": 2,
                         "storm": "not-a-dict"})
    comp = w.report()["ranks"]["1"]["compute"]
    assert comp == {"recompiles": 2}
    assert w.report()["ranks"]["1"]["flags"] == []
    w.ingest_compute(-1, _storm_status_doc())   # bad rank: dropped
    assert "-1" not in w.report()["ranks"]


def test_render_compute_pane_replica_shape():
    top = _load_top()
    pj = compute.profiled_jit(lambda x: x, site="t.pane")
    pj(jnp.zeros((2,), jnp.float32))
    compute.sample_hbm()
    lines = top.render_compute_pane({"compute": compute.report()})
    text = "\n".join(lines)
    assert "compute  traces=1" in text
    assert "storm=ok" in text
    assert "phases" not in text


def test_render_compute_pane_tracker_shape():
    top = _load_top()
    doc = {"compute": {"ranks": {"0": {"recompiles": 0},
                                 "1": {"recompiles": 5}},
                       "storming_ranks": [1]}}
    (line,) = top.render_compute_pane(doc)
    assert "r0:0" in line and "r1:5" in line
    assert "STORM ranks=[1]" in line
    assert top.render_compute_pane({}) == []
