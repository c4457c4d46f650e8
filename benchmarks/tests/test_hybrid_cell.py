"""The cell PR 31 added, serve-ling3-reason: its files, its program
budget, its costs and its roofline reader, and its rehearsal on the
CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_kda, harness, reduce_trace, traffic

ROOT = harness.ROOT
CELL = "serve-ling3-reason"


def test_the_cells_files_resolve_and_the_model_builds():
    cell = harness.load_cell(CELL)
    assert cell.kind == "serve" and cell.chips == 1
    # not norm_lat_p90: its p90 over about 90 lognormal answers a
    # window spread by 9-16% over seeds on the chip (PERF.md section 6)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"kda_time_share", "kda_decode_state_roofline",
            "kda_state_gb_per_step", "state_slot_occupancy",
            "mla_attn_time_share", "mla_decode_attn_roofline",
            "mla_prefill_attn_roofline", "moe_time_share",
            "moe_pairs_held_share", "compiles_in_window", "programs_warmed",
            "serve_hbm_peak_gb", "serve_device_idle_share"} <= names
    assert not names & {"paged_attn_roofline", "paged_attn_time_share"}
    for m in cell.per_layer:
        assert callable(harness.layer_reader(m["spec"]))
    # the new metrics are this cell's alone: no older cell reports them
    for other in ("serve-axk1-longdoc", "serve-flagship-chat"):
        assert not {m["name"] for m in harness.load_cell(other).per_layer} \
            & {"kda_time_share", "state_slot_occupancy"}
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**cell.config["model"])
    assert cfg.hybrid and cfg.n_experts == 64 and cfg.moe_n_routed == 512
    assert cfg.layer_kinds.count("kda") == 11 == costs_kda.kda_layers(
        cell.config["model"])
    assert harness.reference_for(cell.config).logits_at


def test_the_mix_is_the_issues_letter_for_letter():
    mix = harness.load_cell(CELL).traffic
    assert mix["clients"] == 64 and mix["class_deck"] == 16
    deck = [c["name"] for c in traffic._deck(mix)]
    assert deck.count("p1k") == 15 and deck.count("p8k") == 1
    lengths = {c["name"]: c["length"] for c in mix["prompt_classes"]}
    assert lengths["p1k"] == {"dist": "uniform", "min": 1009, "max": 1024}
    assert lengths["p8k"] == {"dist": "uniform", "min": 8177, "max": 8192}
    assert mix["output"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.5, "min": 256, "max": 1024}
    assert (mix["ramp_seconds"], mix["trace_seconds"],
            mix["check_per_class"]) == (12, 12, 2)


def test_warmup_visits_twenty_programs_and_nothing_is_preempted():
    cell = harness.load_cell(CELL)
    sv = cell.config["serve"]
    bs = sv["block_size"]
    widths = traffic.decode_widths(cell.traffic, bs)
    assert sorted(widths) == list(range(8, 17)) + list(range(64, 73))
    plan = traffic.warmup_requests(cell.traffic, 19648, bs)
    buckets = {-(-len(w["prompt"]) // bs) for w in plan}
    assert sorted(buckets) == [8, 64]
    assert len(widths) + len(buckets) == 20
    # 60 x 2,048 + 4 x 9,216 tokens at most in flight, of 196,608
    clients = [traffic._deck(cell.traffic)[c % 16] for c in range(64)]
    in_flight = sum(-(-(c["length"]["max"] + cell.traffic["output"]["max"])
                      // bs) for c in clients)
    assert in_flight * bs == 159744 and in_flight <= sv["n_blocks"]
    assert sv["max_active"] == cell.traffic["clients"] == 64


def test_costs_are_the_algorithms():
    """One hand-worked shape: Ling-3.0-flash's 32 heads of 128 x 128."""
    model = harness.load_cell(CELL).config["model"]
    assert costs_kda.state_bytes_per_row(model) == 32 * 128 * 128 * 4 \
        == 2097152
    step = costs_kda.kda_state_step_cost(model, 64.0)
    assert step["bytes"] == 64 * 2 * 2097152 == 268435456
    assert step["flops"] == 64 * 6 * 32 * 128 * 128
    assert step["flops"] / step["bytes"] == 0.75  # memory-bound
    # 64 rows x 11 layers: 2.95 GB a decode step, 3.6 ms at 819 GB/s
    assert round(11 * step["bytes"] / 819e9 * 1e3, 1) == 3.6
    scan = costs_kda.kda_chunk_scan_cost(model, 8192)
    per_chunk = 6 * 64 * 64 * 128 + 6 * 64 * 128 * 128
    assert scan["flops"] == 32 * 128 * per_chunk
    assert scan["bytes"] == 8192 * 32 * 128 * 5 * 4
    assert costs_kda.kda_layers({"attention": "mla", "n_layers": 7}) == 0


def test_state_roofline_reads_the_kernel_against_the_counter():
    cell = harness.load_cell(CELL)
    m = next(m for m in cell.per_layer
             if m["name"] == "kda_decode_state_roofline")
    read, params = harness.layer_reader(m["spec"]), m["spec"]["params"]
    model = cell.config["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    per_call = 48 * 2 * 2097152 / 819e9          # 48 live rows a step
    trace = reduce_trace.Trace({0: [
        reduce_trace.Event("kda_state_step.1", "jit(f)/kda/state_step", 0.0,
                           2 * per_call),
        reduce_trace.Event("kda_state_step.2", "jit(f)/kda/state_step", 1.0,
                           1.0 + 2 * per_call),
        reduce_trace.Event("fusion.3", "jit(f)/kda/conv", 1.5, 1.6)]}, [])
    numbers = {"counters.serving.paged_decode_steps": 100.0,
               "counters.serving.kda_state_rw_bytes":
                   100.0 * 48 * 11 * 2 * 2097152}
    obs = {"reduction": reduce_trace.Reduction(trace, 0.0, 2.0),
           "model": model, "peaks": peaks, "numbers": numbers}
    assert read(obs, params) == pytest.approx(50.0)
    assert obs["notes"]["kda_state_step_cost"] == "memory-bound"
    # a program without the counter (the parent), an untraced run, or
    # another model: nothing to read, and no exception
    assert read(dict(obs, numbers={}), params) is None
    assert read(dict(obs, reduction=None), params) is None
    assert read(dict(obs, model={"n_heads": 16, "n_layers": 16}),
                params) is None
    # the two counter ratios over the same numbers
    for name, want in (("kda_state_gb_per_step", 48 * 11 * 2 * 2097152e-9),
                       ("state_slot_occupancy", 75.0)):
        spec = next(m for m in cell.per_layer if m["name"] == name)["spec"]
        assert harness.layer_reader(spec)(obs, spec["params"]) \
            == pytest.approx(want)


@pytest.mark.slow
def test_the_cell_rehearses_on_the_cpu():
    """rehearse.json overrides only the flagship's field names (d_model
    64, 4 heads of 16, 2 layers, vocab 512): the two layers left are
    both KDA (published 1 and 2), so the pool has no layer and the
    slots do the work; the run reaches its end (about seven minutes:
    1k and 8k prompts and 1,800 decode steps alone through the lax
    forms)."""
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL,
         "--rehearse", "--seed", "3000000001", "--seconds", "4",
         "--trace", "1"], cwd=ROOT, capture_output=True, text=True,
        timeout=3000, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""  # a rehearsal prints no result line
    line = next(l for l in proc.stderr.splitlines() if "REHEARSAL" in l)
    doc = json.loads(line[line.index("{"):])
    assert doc["metrics"]["programs_warmed"]["value"] == 20
    assert {"kda_time_share", "moe_time_share"} <= set(doc["metrics"])
    assert "the same request sent twice alone returned the same ids: True" \
        in proc.stderr
