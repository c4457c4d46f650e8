"""Cells are data found by name; a later PR adds files and entries and
edits nothing that exists."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import harness, readers, reduce_trace

ROOT = harness.ROOT


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_has_the_contracts_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"]
    assert [w["chips"] for w in b["workloads"]].count(4) == 1
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        # a per-layer metric is reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200


@pytest.mark.parametrize("cell_name", [w["name"] for w in
                                       _bench()["workloads"]])
def test_every_cells_files_resolve_by_name(cell_name):
    cell = harness.load_cell(cell_name)
    assert cell.config["kind"] == cell.traffic["kind"] == cell.kind
    assert harness.runner_for(cell.kind).run
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        # its own file, or the one its kind's name shares
        assert m["name"] in (m["spec"]["name"],
                             f"{cell.kind}_{m['spec']['name']}")
        assert callable(harness.layer_reader(m["spec"]))
        assert m["moves"] in names
    model = cell.config["model"]
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**model)
    # the configuration is the program's published flagship, whole
    assert cfg == tfm.flagship_config()
    assert cell.config["reduced"] == []


def test_a_later_pr_adds_a_cell_as_files_and_entries_only(tmp_path):
    """A made-up fifth cell with its own configuration, traffic mix and
    trace-read per-layer metric, added to a copy of the benchmark as new
    files and new entries: no file that existed is edited, and the
    harness picks all of it up."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    here = os.path.join(root, "benchmarks")
    config = harness._load(os.path.join(
        here, "configs", "flagship-1b-serve.json"))
    config["serve"]["max_active"] = 16
    with open(os.path.join(here, "configs", "made-up-serve.json"), "w") as f:
        json.dump(config, f)
    mix = {"kind": "serve", "loop": "closed", "clients": 8,
           "prompt_classes": [{"name": "tail", "weight": 1.0, "length": {
               "dist": "lognormal", "median": 192, "sigma": 0.8,
               "min": 32, "max": 512}}],
           "class_deck": 1,
           "output": {"dist": "uniform", "min": 8, "max": 16},
           "ramp_seconds": 2, "trace_seconds": 2, "check_per_class": 2}
    with open(os.path.join(here, "traffic", "made-up-tail.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(here, "layer_metrics",
                           "unembed_time_share.json"), "w") as f:
        json.dump({"name": "unembed_time_share", "reader": "trace_share",
                   "params": {"patterns": ["(^|/)unembed(/|$)"],
                              "field": "path", "over": "busy"}}, f)
    bench = _bench()
    bench["configs"].append({
        "name": "made-up-serve", "source": "a test",
        "file": "benchmarks/configs/made-up-serve.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "serve-made-up", "config": "made-up-serve",
        "traffic": "made-up-tail", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append("serve-made-up")
    bench["per_layer"].append({
        "name": "unembed_time_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": ["serve-made-up"]})
    for m in bench["per_layer"]:
        if m["name"] == "serve_device_idle_share":
            m["workloads"].append("serve-made-up")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.load_cell("serve-made-up", root=root)
    assert cell.config["serve"]["max_active"] == 16
    assert cell.traffic["clients"] == 8
    assert [m["name"] for m in cell.per_layer
            if m.get("workloads") == ["serve-made-up"]] == \
        ["unembed_time_share"]
    # a reading every kind has is found under the kind's name
    assert next(m for m in cell.per_layer if m["name"] ==
                "serve_device_idle_share")["spec"]["name"] == \
        "device_idle_share"
    # the new metric reads a trace with no code of its own
    trace = reduce_trace.Trace({0: [
        reduce_trace.Event("fusion.1", "jit(f)/unembed/dot_general", 0, 1),
        reduce_trace.Event("fusion.2", "jit(f)/mlp/dot_general", 1, 4)]}, [])
    m = next(m for m in cell.per_layer if m["name"] == "unembed_time_share")
    value = harness.layer_reader(m["spec"])(
        {"reduction": reduce_trace.Reduction(trace, 0, 4)},
        m["spec"]["params"])
    assert value == pytest.approx(25.0)
    # the general generator takes the new mix as it is
    from benchmarks import traffic

    assert 32 <= len(traffic.request("made-up-tail", cell.traffic, 1,
                                     32768, 3, 0)["prompt"]) <= 512
    assert traffic.warmup_requests(cell.traffic, 32768, 16)
    # and nothing that was there has changed
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, path


def test_readers_leave_out_what_they_cannot_read():
    obs = {"numbers": {"facts.window_s": 10.0}, "reduction": None,
           "sites": {"open": {}, "close": {}}, "responses": []}
    assert readers.ratio(obs, {"num": ["facts.missing"]}) is None
    assert readers.ratio(obs, {"num": ["facts.window_s", -4],
                               "den": [2], "scale": 100}) == 300.0
    assert readers.trace_share(obs, {"patterns": ["x"]}) is None
    assert readers.kernel_roofline(obs, {"patterns": ["x"],
                                         "cost_fn": "flash_fwd_cost"}) is None
    assert readers.response_percentile(obs, {"field": "ttft_s",
                                             "q": 50}) is None
    assert readers.site_stat(obs, {"field": "traces", "at": "open"}) is None


def test_kv_pool_occupancy_is_blocks_in_use_over_the_pool():
    cell = harness.load_cell("serve-flagship-doc")
    m = next(m for m in cell.per_layer if m["name"] == "kv_pool_occupancy")
    read = harness.layer_reader(m["spec"])
    obs = {"numbers": {"facts.pool_blocks_in_use": 640.0,
                       "facts.pool_blocks": 2560.0}}
    assert read(obs, m["spec"]["params"]) == 25.0
    assert read({"numbers": {}}, m["spec"]["params"]) is None


def test_site_stat_reads_the_windows_edges():
    obs = {"sites": {"open": {"a": {"traces": 2, "signatures": 2},
                              "b": {"traces": 1, "signatures": 1}},
                     "close": {"a": {"traces": 3, "signatures": 3},
                               "b": {"traces": 1, "signatures": 1}}}}
    assert readers.site_stat(obs, {"field": "signatures",
                                   "at": "open"}) == 3.0
    assert readers.site_stat(obs, {"field": "traces",
                                   "at": "window"}) == 1.0


def test_kernel_roofline_per_step_and_per_call():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    model = {"n_heads": 1, "head_dim": 1, "dtype": "bfloat16"}
    trace = reduce_trace.Trace({0: [
        reduce_trace.Event("paged_attn.1", "", 0.0, 2.0),
        reduce_trace.Event("paged_attn.2", "", 3.0, 5.0)]}, [])
    obs = {"reduction": reduce_trace.Reduction(trace, 0.0, 6.0),
           "model": model, "peaks": peaks,
           "numbers": {"facts.ctx_tokens_per_decode_step": 5.0}}
    # per call: 5 tokens x 2 (K, V) x 2 bytes = 20 bytes -> 2 s at the
    # peak; two calls took 4 s
    got = readers.kernel_roofline(obs, {"patterns": ["paged_attn"],
                                        "cost_fn": "paged_attn_cost",
                                        "args": "context"})
    assert got == pytest.approx(100.0)
    assert obs["notes"]["paged_attn_cost"] == "memory-bound"


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    paths, the command exits non-zero and prints no result."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    str(tmp_path / "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "train-flagship-t1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=str(tmp_path), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_no_result_on_a_cpu():
    """A run that finds no TPU fails; there is no CPU result line."""
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "train-flagship-t1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
