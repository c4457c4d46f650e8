"""The cell PR 33 added, serve-cmdaplus-rag: its files, its program
budget, its costs and its readers.  No rehearsal on the CPU:
rehearse.json's toy has 4 query heads, which the cell's 8 K/V heads
cannot group (PERF.md section 7 asks a ``benchmark`` issue for a toy
``n_kv_heads``); tests/test_cohere2_family.py drives the family's
programs and the engine at a small size instead."""

import pytest

from benchmarks import costs_gqa_swa, harness, reduce_trace, traffic

CELL = "serve-cmdaplus-rag"
NEW = {"attn_full_time_share", "attn_sliding_time_share",
       "gqa_prefill_attn_roofline", "gqa_decode_attn_roofline",
       "kv_sliding_pool_occupancy", "kv_kb_per_cached_token"}


def test_the_cells_files_resolve_and_the_model_builds():
    cell = harness.load_cell(CELL)
    assert cell.kind == "serve" and cell.chips == 1
    # not norm_lat_p90: a window answers some 35-40 requests, and a
    # 90th percentile over so few is nearly a maximum
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"moe_time_share", "moe_pairs_held_share",
                  "moe_expert_load_max_over_mean", "compiles_in_window",
                  "programs_warmed", "serve_hbm_peak_gb", "kv_pool_occupancy",
                  "serve_device_idle_share"} <= names
    # costs.py counts MHA pages, and the cell has no latent or KDA layer
    assert not names & {"paged_attn_roofline", "paged_attn_time_share",
                        "mla_attn_time_share", "kda_time_share"}
    for m in cell.per_layer:
        assert callable(harness.layer_reader(m["spec"]))
    # the new metrics are this cell's alone: no older cell reports them
    for other in ("serve-axk1-longdoc", "serve-flagship-chat",
                  "serve-ling3-reason"):
        assert not {m["name"] for m in harness.load_cell(other).per_layer} \
            & NEW
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**cell.config["model"])
    assert cfg.family == "mha_swa" and cfg.kv_heads == 8
    assert cfg.n_experts == 16 and cfg.moe_n_routed == 128
    assert list(cfg.layer_kinds) == costs_gqa_swa.layer_kinds(
        cell.config["model"]) == ["sliding"] * 3 + ["full"]
    assert harness.reference_for(cell.config).logits_at


def test_the_mix_is_the_issues_letter_for_letter():
    mix = harness.load_cell(CELL).traffic
    assert mix["loop"] == "closed"
    assert mix["clients"] == 8 and mix["class_deck"] == 8
    deck = [c["name"] for c in traffic._deck(mix)]
    assert deck.count("p16k") == 5 and deck.count("p32k") == 3
    lengths = {c["name"]: c["length"] for c in mix["prompt_classes"]}
    assert lengths["p16k"] == {"dist": "uniform", "min": 16369, "max": 16384}
    assert lengths["p32k"] == {"dist": "uniform", "min": 32753, "max": 32768}
    assert mix["output"] == {"dist": "uniform", "min": 64, "max": 128}
    assert (mix["ramp_seconds"], mix["trace_seconds"],
            mix["check_per_class"]) == (12, 12, 2)


def test_warmup_visits_six_programs_and_nothing_is_preempted():
    cell = harness.load_cell(CELL)
    sv = cell.config["serve"]
    bs = sv["block_size"]
    widths = traffic.decode_widths(cell.traffic, bs)
    assert sorted(widths) == [128, 129, 256, 257]
    plan = traffic.warmup_requests(cell.traffic, 32768, bs)
    buckets = {-(-len(w["prompt"]) // bs) for w in plan}
    assert sorted(buckets) == [128, 256]
    assert len(widths) + len(buckets) == 6
    # 5 x 129 + 3 x 257 blocks at most in flight in the full pool (ISSUE
    # 33 counts a block of headroom a row: 1,424); the sliding pool has
    # a ring for every row whatever its context
    in_flight = sum(-(-(c["length"]["max"] + cell.traffic["output"]["max"])
                      // bs) for c in traffic._deck(cell.traffic))
    assert in_flight == 1416 <= sv["n_blocks"]
    assert sv["max_active"] == cell.traffic["clients"] == 8
    # kv_sliding_pool_occupancy's scale is 100 over the pool's blocks
    spec = next(m for m in cell.per_layer
                if m["name"] == "kv_sliding_pool_occupancy")["spec"]
    ring = -(-cell.config["model"]["sliding_window"] // bs) + 1
    assert spec["params"]["scale"] == pytest.approx(
        100 / (sv["max_active"] * ring))


def test_costs_are_the_algorithms():
    """Hand-worked: 128 query heads on 8 K/V heads of 128, window 4096."""
    model = harness.load_cell(CELL).config["model"]
    assert costs_gqa_swa.visible_pairs(4, 0) == 10
    assert costs_gqa_swa.visible_pairs(4, 2) == 1 + 2 + 2 + 2
    assert costs_gqa_swa.visible_pairs(3, 8) == 6
    full = costs_gqa_swa.gqa_prefill_attn_cost(model, 32768, 0)
    assert full["flops"] == 4 * (32768 * 32769 / 2) * 128 * 128
    assert round(full["flops"] / 32768 / 1e9, 2) == 1.07  # a token
    assert full["bytes"] == 32768 * 128 * (2 * 128 + 2 * 8) * 2
    win = costs_gqa_swa.gqa_prefill_attn_cost(model, 32768, 4096)
    assert win["flops"] == 4 * (4096 * 4097 / 2 + 28672 * 4096) * 128 * 128
    assert round(win["flops"] / full["flops"], 2) == 0.23
    assert round(costs_gqa_swa.gqa_prefill_attn_cost(
        model, 16384, 4096)["flops"] / costs_gqa_swa.gqa_prefill_attn_cost(
            model, 16384, 0)["flops"], 2) == 0.44
    assert win["bytes"] == full["bytes"]
    assert costs_gqa_swa.kv_bytes_per_token(model) == 4096
    dec = costs_gqa_swa.gqa_decode_attn_cost(model, 1000.0)
    assert dec["bytes"] == 1000 * 4096
    assert dec["flops"] == 1000 * 4 * 128 * 128
    assert dec["flops"] / dec["bytes"] == 16  # memory-bound
    assert costs_gqa_swa.layer_kinds({"n_layers": 16}) == []


def _reader(name):
    cell = harness.load_cell(CELL)
    m = next(m for m in cell.per_layer if m["name"] == name)
    return harness.layer_reader(m["spec"]), m["spec"]["params"], cell


def test_prefill_roofline_counts_each_call_by_its_own_scope():
    read, params, cell = _reader("gqa_prefill_attn_roofline")
    model = cell.config["model"]
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e15}
    least = {(t, w): costs_gqa_swa.gqa_prefill_attn_cost(
        model, t, w)["flops"] / 1e12
        for t, w in ((16384, 0), (32768, 4096))}
    path = ("jit(f)/attn_{}/prefill_attn_t{}_w{}_c{}/flash_fwd_o/"
            "pallas_call")
    quarter = least[(32768, 4096)] / 4
    trace = reduce_trace.Trace({0: [
        # a full layer at 16k in two chunks, each twice its least time
        reduce_trace.Event("flash_fwd_o.1", path.format(
            "full", 16384, 0, 2), 0.0, least[(16384, 0)]),
        reduce_trace.Event("flash_fwd_o.2", path.format(
            "full", 16384, 0, 2), 100.0, 100.0 + least[(16384, 0)]),
        # one of a sliding layer's four chunks at 32k, four times
        reduce_trace.Event("flash_fwd_o.3", path.format(
            "sliding", 32768, 4096, 4), 300.0, 300.0 + 4 * quarter),
        reduce_trace.Event("fusion.4", "jit(f)/attn_full/dot", 500.0,
                           501.0)]}, [])
    obs = {"reduction": reduce_trace.Reduction(trace, 0.0, 1000.0),
           "model": model, "peaks": peaks, "numbers": {}}
    want = 100 * (least[(16384, 0)] + quarter) / (
        2 * least[(16384, 0)] + 4 * quarter)
    assert read(obs, params) == pytest.approx(want)
    assert obs["notes"]["gqa_prefill_attn_cost"] == "compute-bound"
    # an untraced run, a program without the scope (the parent), or
    # another model: nothing to read, and no exception
    assert read(dict(obs, reduction=None), params) is None
    assert read(dict(obs, model={"n_heads": 16}), params) is None
    bare = reduce_trace.Trace({0: [reduce_trace.Event(
        "flash_fwd_o.1", "jit(f)/mla/prefill_attn_t8192/flash_fwd_o", 0.0,
        1.0)]}, [])
    assert read(dict(obs, reduction=reduce_trace.Reduction(bare, 0.0, 2.0)),
                params) is None


def test_decode_roofline_sums_both_kinds_of_layer_from_the_counters():
    read, params, cell = _reader("gqa_decode_attn_roofline")
    model = cell.config["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 8 rows of 24,576 tokens: a full layer attends 196,608 keys a
    # step, a sliding one 8 x 4,096
    full_s = 196608 * 4096 / 819e9
    sliding_s = 32768 * 4096 / 819e9
    events, at = [], 0.0
    for step in range(2):
        for took in (sliding_s, sliding_s, sliding_s, full_s):
            events.append(reduce_trace.Event(
                f"paged_attn.{len(events)}", "jit(f)/attn/paged_attn", at,
                at + 2 * took))
            at += 1.0
    events.append(reduce_trace.Event("fusion.9", "jit(f)/attn_full/dot",
                                     at, at + 0.5))
    numbers = {"counters.serving.paged_decode_steps": 50.0,
               "counters.serving.attn_full_ctx_tokens": 50.0 * 196608,
               "counters.serving.attn_sliding_ctx_tokens": 50.0 * 32768}
    obs = {"reduction": reduce_trace.Reduction(
        reduce_trace.Trace({0: events}, []), 0.0, at + 1.0),
        "model": model, "peaks": peaks, "numbers": numbers}
    assert read(obs, params) == pytest.approx(50.0)
    assert obs["notes"]["gqa_decode_attn_cost"] == "memory-bound"
    assert read(dict(obs, numbers={}), params) is None       # the parent
    assert read(dict(obs, reduction=None), params) is None
    assert read(dict(obs, model={"n_heads": 16, "n_layers": 16}),
                params) is None


def test_the_cache_readers_weigh_blocks_by_their_layers():
    read, params, cell = _reader("kv_kb_per_cached_token")
    # 100 steps of 8 rows at 32k: 257 full blocks and a ring of 33 each
    numbers = {"counters.serving.kv_block_steps": 100.0 * 8 * 257,
               "counters.serving.kv_sliding_block_steps": 100.0 * 8 * 33,
               "counters.serving.kv_cached_token_steps": 100.0 * 8 * 32800,
               "counters.serving.paged_decode_steps": 100.0}
    obs = {"numbers": numbers, "model": cell.config["model"],
           "config": cell.config}
    block = 128 * 4096
    want = (257 * block + 33 * 3 * block) / 32800 / 1024
    assert read(obs, params) == pytest.approx(want)
    assert 5.5 < want < 5.6  # against 16 for four full layers
    assert read(dict(obs, numbers={}), params) is None
    assert read(dict(obs, model={"n_layers": 16, "n_heads": 16}),
                params) is None
    occupancy, o_params, _ = _reader("kv_sliding_pool_occupancy")
    assert occupancy(obs, o_params) == pytest.approx(100.0)
    assert occupancy(dict(obs, numbers={}), o_params) is None
