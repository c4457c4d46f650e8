"""The cell PR 27 added, serve-axk1-longdoc: its files, its costs, its
two roofline readers, and its rehearsal on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_mla_moe, harness, reduce_trace, traffic

ROOT = harness.ROOT
CELL = "serve-axk1-longdoc"


def test_the_cells_files_resolve_and_the_model_builds():
    cell = harness.load_cell(CELL)
    assert cell.kind == "serve" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"mla_attn_time_share", "moe_time_share", "moe_pairs_held_share",
            "moe_expert_load_max_over_mean", "mla_prefill_attn_roofline",
            "mla_decode_attn_roofline", "serve_hbm_peak_gb"} <= names
    assert not names & {"paged_attn_roofline", "paged_attn_time_share",
                        "compiles_in_window", "programs_warmed"}
    for m in cell.per_layer:
        assert callable(harness.layer_reader(m["spec"]))
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**cell.config["model"])
    assert cfg.latent and cfg.n_experts == 12 and cfg.moe_n_routed == 192
    assert harness.reference_for(cell.config).logits_at


def test_the_mix_stays_inside_its_program_budget():
    cell = harness.load_cell(CELL)
    sv = cell.config["serve"]
    for bs in (16, 32, 64, 128):
        assert len(traffic.decode_widths(cell.traffic, bs)) <= 6
    plan = traffic.warmup_requests(cell.traffic, 20480, sv["block_size"])
    assert sorted({-(-len(w["prompt"]) // sv["block_size"]) for w in plan}
                  ) == [64, 128]
    # 5 x 8,224 + 3 x 16,416 tokens at most in flight: no preemption
    in_flight = sum(
        -(-(c["length"]["max"] + cell.traffic["output"]["max"])
          // sv["block_size"]) for c in traffic._deck(cell.traffic))
    assert in_flight <= sv["n_blocks"]


def test_costs_are_the_algorithms():
    model = harness.load_cell(CELL).config["model"]
    pre = costs_mla_moe.mla_prefill_attn_cost(model, 16384)
    assert pre["flops"] == 2 * 16384 ** 2 / 2 * 64 * (192 + 128)
    dec = costs_mla_moe.mla_decode_attn_cost(model, 1000.0)
    assert dec["bytes"] == 1000 * 576 * 2
    assert dec["flops"] == 1000 * 4 * 64 * 544


def _reader(name):
    cell = harness.load_cell(CELL)
    m = next(m for m in cell.per_layer if m["name"] == name)
    return harness.layer_reader(m["spec"]), m["spec"]["params"], \
        cell.config["model"]


def test_prefill_roofline_counts_each_call_at_its_own_length():
    read, params, model = _reader("mla_prefill_attn_roofline")
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    least = {t: costs_mla_moe.mla_prefill_attn_cost(model, t)["flops"] / 1e12
             for t in (8192, 16384)}
    path = "jit(f)/mla/prefill_attn_t{}/flash_fwd_o/pallas_call"
    trace = reduce_trace.Trace({0: [
        reduce_trace.Event("flash_fwd_o.1", path.format(8192), 0.0,
                           2 * least[8192]),
        reduce_trace.Event("flash_fwd_o.2", path.format(16384), 10.0,
                           10.0 + 4 * least[16384]),
        reduce_trace.Event("fusion.3", "jit(f)/mla/dot", 50.0, 51.0)]}, [])
    obs = {"reduction": reduce_trace.Reduction(trace, 0.0, 60.0),
           "model": model, "peaks": peaks, "numbers": {}}
    want = 100 * (least[8192] + least[16384]) / (
        2 * least[8192] + 4 * least[16384])
    assert read(obs, params) == pytest.approx(want)
    assert obs["notes"]["mla_prefill_attn_cost"] == "compute-bound"
    # a program without the scope (the parent), or another model: nothing
    assert read(dict(obs, reduction=None), params) is None
    assert read(dict(obs, model={"n_heads": 16}), params) is None


def test_decode_roofline_reads_the_latent_kernel():
    read, params, model = _reader("mla_decode_attn_roofline")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    per_call = 90000 * 576 * 2 / 819e9
    trace = reduce_trace.Trace({0: [
        reduce_trace.Event("mla_paged_attn.1", "", 0.0, 4 * per_call),
        reduce_trace.Event("mla_paged_attn.2", "", 1.0, 1.0 + 4 * per_call),
    ]}, [])
    obs = {"reduction": reduce_trace.Reduction(trace, 0.0, 2.0),
           "model": model, "peaks": peaks,
           "numbers": {"facts.ctx_tokens_per_decode_step": 90000.0}}
    assert read(obs, params) == pytest.approx(25.0)
    assert obs["notes"]["mla_decode_attn_cost"] == "memory-bound"
    assert read(dict(obs, numbers={}), params) is None


@pytest.mark.slow
def test_the_cell_rehearses_on_the_cpu():
    """rehearse.json overrides only the flagship's field names (d_model
    64, 4 heads, 2 layers, vocab 512): the latent fields work beside
    them, and the run reaches its end (about six minutes: 8k and 16k
    prompts through the lax twins)."""
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL,
         "--rehearse", "--seed", "3000000001", "--seconds", "4",
         "--trace", "1"], cwd=ROOT, capture_output=True, text=True,
        timeout=3000, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""  # a rehearsal prints no result line
    line = next(l for l in proc.stderr.splitlines() if "REHEARSAL" in l)
    doc = json.loads(line[line.index("{"):])
    # the scope readers always read; the counter ratios only where a
    # prefill's span closed inside so short a window
    assert {"mla_attn_time_share", "moe_time_share"} <= set(doc["metrics"])
    assert "the same request sent twice alone returned the same ids: True" \
        in proc.stderr
