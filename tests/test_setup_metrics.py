"""Set-up from inside (PR 36): the per-layer metrics BENCHMARK.json
gained resolve by name through ``harness.layer_reader`` and read a
number from what a run observes, recorded here from the real program
at a toy size; on a program without the spans (the parent of the PR
that added them) they read nothing, or 0, and never raise."""

import json
import os

import jax.numpy as jnp
import pytest

from benchmarks import harness, measure, reduce_trace
from dmlc_tpu import telemetry
from dmlc_tpu.telemetry import compute

SETUP = ("setup_trace_s", "setup_lower_s", "setup_backend_s",
         "setup_first_call_s", "setup_cache_misses")
SERVE_ONLY = ("setup_engine_init_s", "prefill_fetch_ms",
              "idle_in_prefill_fetch_share")
MS = 1e-3


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    compute.reset_compute()
    yield
    telemetry.reset()
    compute.reset_compute()


def _entries():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, {m["name"]: m for m in bench["per_layer"]}


def _recorded_obs():
    """What ``harness.observations`` hands the readers, from a program
    that compiled one site, built its engine and ran two prefills
    before the window and two inside it."""
    pj = compute.profiled_jit(lambda x: jnp.tanh(x) + 1.0, site="t.setup")
    pj(jnp.ones((4,), jnp.float32))
    with telemetry.span("serving.engine_init", stage="serving"):
        pass

    def prefill():
        with telemetry.span("serving.prefill.fetch", stage="serving"):
            pass

    prefill(), prefill()
    before = harness.program_state()
    prefill(), prefill()
    after = harness.program_state()
    numbers = measure.delta(after["counters"], before["counters"])
    # the device idles 2-6 ms of a 10 ms window; the blocking read of a
    # prefill spans 3-5 ms of it
    trace = reduce_trace.Trace(
        {0: [reduce_trace.Event("fusion.1", "", 0.0, 2 * MS),
             reduce_trace.Event("fusion.2", "", 6 * MS, 10 * MS)]},
        [reduce_trace.Event("serving.prefill.run", "", 2.5 * MS, 5 * MS,
                            "serving-engine"),
         reduce_trace.Event("serving.prefill.fetch", "", 3 * MS, 5 * MS,
                            "serving-engine")])
    return {"numbers": numbers,
            "sites": {"open": before["sites"], "close": after["sites"]},
            "reduction": reduce_trace.Reduction(trace, 0.0, 10 * MS)}


def _read(name, obs):
    cell = harness.load_cell("serve-flagship-chat")
    (m,) = [m for m in cell.per_layer if m["name"] == name]
    assert m["spec"]["name"] == name
    return harness.layer_reader(m["spec"])(obs, m["spec"].get("params", {}))


@pytest.mark.parametrize("name", SETUP + SERVE_ONLY)
def test_new_layer_metric_resolves_and_reads_a_number(name):
    obs = _recorded_obs()
    value = _read(name, obs)
    assert isinstance(value, float)
    site = obs["sites"]["open"]["t.setup"]
    serving = telemetry.counters_snapshot()["serving"]
    want = {
        "setup_trace_s": site["trace_secs_total"],
        "setup_lower_s": site["lower_secs_total"],
        "setup_backend_s": site["backend_secs_total"],
        "setup_first_call_s": site["first_call_secs_total"],
        "setup_cache_misses": 0.0,
        "setup_engine_init_s": serving["engine_init_secs"],
        # the window's two reads, not the four of the process
        "prefill_fetch_ms": 1000.0 * obs["numbers"][
            "counters.serving.prefill_fetch_secs"] / 2,
        "idle_in_prefill_fetch_share": 20.0,
    }[name]
    assert value == pytest.approx(want)
    if name.endswith("_s") or name.endswith("_ms"):
        assert value > 0


def test_phase_sums_equal_the_sites_compile_seconds():
    obs = _recorded_obs()
    total = sum(s["compile_secs_total"]
                for s in obs["sites"]["open"].values())
    phases = sum(_read(n, obs) for n in SETUP[:3])
    assert total > 0 and phases == pytest.approx(total, rel=0.01)


def test_entries_list_the_cells_the_issue_names():
    bench, entries = _entries()
    cells = [w["name"] for w in bench["workloads"]]
    serve = [c for c in cells if c.startswith("serve-")]
    # two catalog serve cells came after these metrics
    assert len(cells) == 9 and len(serve) == 7
    for name in SETUP:
        m = entries[name]
        assert (m["layer"], m["moves"], m["better"]) \
            == ("entry", "setup_s", "lower")
        assert m["workloads"] == cells
    assert entries["setup_engine_init_s"]["workloads"] == serve
    assert entries["setup_engine_init_s"]["moves"] == "setup_s"
    for name in SERVE_ONLY[1:]:
        m = entries[name]
        assert (m["layer"], m["moves"], m["workloads"]) \
            == ("engine", "serve_tok_s", serve)
    # appended: what was there keeps its place, and so do these eight
    # under what later PRs append after them
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(SETUP[0])
    assert first == 53 and names[first:first + 8] == list(SETUP + SERVE_ONLY)


def test_on_a_program_without_the_spans_nothing_raises():
    """The parent's program under these files: its sites have no
    seconds by phase, its counters no new pairs."""
    old_site = {"traces": 3, "hits": 9, "recompiles": 2,
                "aot_fallbacks": 0, "compile_secs_total": 4.5,
                "signatures": 3}
    trace = reduce_trace.Trace(
        {0: [reduce_trace.Event("fusion.1", "", 0.0, 2 * MS)]},
        [reduce_trace.Event("serving.prefill.run", "", 2 * MS, 5 * MS,
                            "serving-engine")])
    obs = {"numbers": {"counters.serving.prefill_secs": 1.0},
           "sites": {"open": {"serving.prefill": old_site},
                     "close": {"serving.prefill": old_site}},
           "reduction": reduce_trace.Reduction(trace, 0.0, 10 * MS)}
    for name in SETUP:
        assert _read(name, obs) == 0.0
    assert _read("setup_engine_init_s", obs) is None
    assert _read("prefill_fetch_ms", obs) is None
    assert _read("idle_in_prefill_fetch_share", obs) == 0.0
    # an untraced run has no reduction: left out
    assert _read("idle_in_prefill_fetch_share",
                 dict(obs, reduction=None)) is None
