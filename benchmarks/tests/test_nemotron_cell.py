"""The cell PR 38 added, serve-nemotron3-agents: its files, its program
budget, its costs and its roofline reader.  It has no rehearsal on the
CPU: rehearse.json overrides the flagship's field names alone, which
leaves the Mamba-2 and expert widths as published, and the warm-up's
first request (8k tokens, then 642 decode steps alone) outlasts the
runner's 300 s a request there (tried in PR 38);
tests/test_nemotron_h_family.py drives the programs and the engine at
a small size instead."""

import pytest

from benchmarks import costs_mamba2, harness, reduce_trace, traffic

CELL = "serve-nemotron3-agents"
NEW = {"mamba_time_share", "ssm_decode_state_roofline",
       "ssm_state_gb_per_step", "ssm_slot_occupancy"}


def test_the_cells_files_resolve_and_the_model_builds():
    cell = harness.load_cell(CELL)
    assert cell.kind == "serve" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"moe_time_share", "moe_pairs_held_share",
                  "moe_expert_load_max_over_mean", "attn_full_time_share",
                  "compiles_in_window", "programs_warmed",
                  "serve_hbm_peak_gb", "serve_device_idle_share",
                  "setup_engine_init_s"} <= names
    # readers that find nothing in this model stay off the cell: KDA's
    # and MLA's, the flagship's paged kernel's, and the grouped-head
    # rooflines, which read a sliding window's counters
    assert not names & {"kda_time_share", "state_slot_occupancy",
                        "mla_attn_time_share", "paged_attn_roofline",
                        "gqa_decode_attn_roofline",
                        "gqa_prefill_attn_roofline", "norm_lat_p90"}
    for m in cell.per_layer:
        assert callable(harness.layer_reader(m["spec"]))
    # the new metrics are this cell's alone: no older cell reports them
    for other in ("serve-ling3-reason", "serve-cmdaplus-rag"):
        assert not {m["name"] for m in harness.load_cell(other).per_layer} \
            & NEW
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**cell.config["model"])
    assert cfg.family == "nemotron_h" and cfg.n_experts == 128 \
        and cfg.moe_n_routed == 512 and cfg.moe_topk == 22
    assert cfg.layer_kinds.count("mamba") == 5 == costs_mamba2.mamba_layers(
        cell.config["model"])
    assert harness.reference_for(cell.config).logits_at


def test_the_mix_is_the_issues_letter_for_letter():
    mix = harness.load_cell(CELL).traffic
    assert mix["clients"] == 128 and mix["class_deck"] == 16
    deck = [c["name"] for c in traffic._deck(mix)]
    assert deck.count("p1k") == 15 and deck.count("p8k") == 1
    lengths = {c["name"]: c["length"] for c in mix["prompt_classes"]}
    assert lengths["p1k"] == {"dist": "uniform", "min": 1009, "max": 1024}
    assert lengths["p8k"] == {"dist": "uniform", "min": 8177, "max": 8192}
    assert mix["output"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.5, "min": 128, "max": 768}
    assert (mix["trace_seconds"], mix["check_per_class"]) == (12, 2)


def test_warmup_visits_sixteen_programs_and_nothing_is_preempted():
    cell = harness.load_cell(CELL)
    sv = cell.config["serve"]
    bs = sv["block_size"]
    widths = traffic.decode_widths(cell.traffic, bs)
    assert sorted(widths) == list(range(8, 15)) + list(range(64, 71))
    plan = traffic.warmup_requests(cell.traffic, 32768, bs)
    buckets = {-(-len(w["prompt"]) // bs) for w in plan}
    assert sorted(buckets) == [8, 64]
    assert len(widths) + len(buckets) == 16
    # 120 x 1,792 + 8 x 8,960 tokens at most in flight, of 294,912
    clients = [traffic._deck(cell.traffic)[c % 16] for c in range(128)]
    in_flight = sum(-(-(c["length"]["max"] + cell.traffic["output"]["max"])
                      // bs) for c in clients)
    assert in_flight * bs == 286720 and in_flight <= sv["n_blocks"]
    assert sv["max_active"] == sv["queue_depth"] \
        == cell.traffic["clients"] == 128


def test_costs_are_the_algorithms():
    """One hand-worked shape: 128 heads of 64 x 128 in 8 groups."""
    model = harness.load_cell(CELL).config["model"]
    assert costs_mamba2.state_bytes_per_row(model) == 128 * 64 * 128 * 4 \
        == 4194304
    step = costs_mamba2.ssm_state_step_cost(model, 128.0)
    assert step["bytes"] == 128 * 2 * 4194304 == 1073741824
    assert step["flops"] == 128 * 5 * 128 * 64 * 128
    assert step["flops"] / step["bytes"] == 0.625  # memory-bound
    # 128 rows x 5 layers: 5.37 GB a decode step, 6.6 ms at 819 GB/s
    assert round(5 * step["bytes"] / 1e9, 2) == 5.37
    assert round(5 * step["bytes"] / 819e9 * 1e3, 1) == 6.6
    scan = costs_mamba2.ssd_chunk_scan_cost(model, 8192)
    per_chunk = 8 * 2 * 128 * 128 * 128 + 128 * (
        128 * 128 + 2 * 128 * 128 * 64 + 4 * 128 * 64 * 128 + 64 * 128)
    assert scan["flops"] == 64 * per_chunk
    assert scan["bytes"] == 8192 * (2 * 8192 + 2 * 1024 + 128) * 4
    assert costs_mamba2.mamba_layers({"attention": "mla", "n_layers": 7}) == 0


def test_state_roofline_reads_the_kernel_against_the_counter():
    cell = harness.load_cell(CELL)
    m = next(m for m in cell.per_layer
             if m["name"] == "ssm_decode_state_roofline")
    read, params = harness.layer_reader(m["spec"]), m["spec"]["params"]
    model = cell.config["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    per_call = 96 * 2 * 4194304 / 819e9          # 96 live rows a step
    trace = reduce_trace.Trace({0: [
        reduce_trace.Event("ssm_state_step.1", "jit(f)/mamba/state_step",
                           0.0, 2 * per_call),
        reduce_trace.Event("ssm_state_step.2", "jit(f)/mamba/state_step",
                           1.0, 1.0 + 2 * per_call),
        reduce_trace.Event("fusion.3", "jit(f)/mamba/conv", 1.5, 1.6)]}, [])
    numbers = {"counters.serving.paged_decode_steps": 100.0,
               "counters.serving.state_slot_steps": 100.0 * 96,
               "counters.serving.ssm_state_rw_bytes":
                   100.0 * 96 * 5 * 2 * 4194304}
    obs = {"reduction": reduce_trace.Reduction(trace, 0.0, 2.0),
           "model": model, "peaks": peaks, "numbers": numbers}
    assert read(obs, params) == pytest.approx(50.0)
    assert obs["notes"]["ssm_state_step_cost"] == "memory-bound"
    # a program without the counter (the parent), an untraced run, or
    # another model: nothing to read, and no exception
    assert read(dict(obs, numbers={}), params) is None
    assert read(dict(obs, reduction=None), params) is None
    assert read(dict(obs, model={"n_heads": 16, "n_layers": 16}),
                params) is None
    # the counter ratios over the same numbers, and the scope's share
    for name, want in (("ssm_state_gb_per_step", 96 * 5 * 2 * 4194304e-9),
                       ("ssm_slot_occupancy", 75.0),
                       ("mamba_time_share",
                        100.0 * (4 * per_call + 0.1) / (4 * per_call + 0.1))):
        spec = next(m for m in cell.per_layer if m["name"] == name)["spec"]
        assert harness.layer_reader(spec)(obs, spec["params"]) \
            == pytest.approx(want)
        assert harness.layer_reader(spec)(
            dict(obs, numbers={}, reduction=None), spec["params"]) is None
