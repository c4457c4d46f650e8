"""The cell serve-ouro-cot: its files, its program budget and in-flight
bound, costs_looped's arithmetic and the readers of the looped
model's per-layer metrics.  tests/test_ouro_family.py drives the programs and the engine
at a small size."""

import pytest

from benchmarks import costs, costs_looped, harness, reduce_trace, traffic

CELL = "serve-ouro-cot"
NEW = {"loop_kv_gb_per_step", "loop_decode_roofline"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_cells_files_resolve_and_the_model_builds():
    cell = harness.load_cell(CELL)
    assert cell.kind == "serve" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"serve_hbm_peak_gb", "serve_device_idle_share",
                  "paged_attn_time_share", "paged_attn_roofline",
                  "compiles_in_window",
                  "programs_warmed", "setup_trace_s", "setup_lower_s",
                  "setup_engine_init_s", "prefill_fetch_ms",
                  "decode_overlap_share"} <= names
    # the other families' readers find nothing here
    assert not names & {"gqa_decode_attn_roofline",
                        "moe_time_share", "kda_time_share",
                        "attn_full_time_share", "norm_lat_p90"}
    for m in cell.per_layer:
        assert callable(harness.layer_reader(m["spec"]))
    for other in ("serve-flagship-chat", "serve-cmdaplus-rag"):
        assert not {m["name"] for m in harness.load_cell(other).per_layer} \
            & NEW
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**cell.config["model"])
    assert cfg.family == "mha" and cfg.loop_passes == 4 and cfg.sandwich_norm
    assert harness.reference_for(cell.config).logits_at


def test_the_mix_and_its_twelve_programs():
    cell = harness.load_cell(CELL)
    mix, sv = cell.traffic, cell.config["serve"]
    assert mix["clients"] == 8 == sv["max_active"] == sv["queue_depth"]
    lengths = {c["name"]: c["length"] for c in mix["prompt_classes"]}
    assert lengths == {"p128": {"dist": "uniform", "min": 113, "max": 128},
                       "p256": {"dist": "uniform", "min": 241, "max": 256}}
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.5, "min": 128, "max": 448}
    deck = [c["name"] for c in traffic._deck(mix)]
    assert deck == ["p128", "p256"]
    bs = sv["block_size"]
    widths = traffic.decode_widths(mix, bs)
    assert sorted(widths) == list(range(2, 12))
    plan = traffic.warmup_requests(mix, 49152, bs)
    buckets = {-(-len(w["prompt"]) // bs) for w in plan}
    assert sorted(buckets) == [2, 4]
    assert len(widths) + len(buckets) == 12
    # 4 x ceil(576 / 64) + 4 x ceil(704 / 64) blocks at most in flight
    clients = [traffic._deck(mix)[c % 2] for c in range(8)]
    in_flight = sum(-(-(c["length"]["max"] + mix["output"]["max"]) // bs)
                    for c in clients)
    assert in_flight == 80 <= sv["n_blocks"] == 92


def test_costs_are_the_algorithms():
    model = harness.load_cell(CELL).config["model"]
    assert costs_looped.passes(model) == 4
    assert costs_looped.cache_layers(model) == 192
    assert costs_looped.kv_bytes_per_token(model) == 1572864
    # 51.39M parameters a layer in bf16, four passes and the head
    assert costs_looped.layer_weight_bytes(model) == 2 * 51388416
    assert costs_looped.decode_weight_bytes(model) == \
        192 * 2 * 51388416 + 2 * 49152 * 2048
    assert round(costs_looped.decode_weight_bytes(model) / 1e9, 2) == 19.93
    # one paged_attn call reads one cache layer: costs.py's MHA call
    call = costs.paged_attn_cost(model, 2500.0)
    assert call["bytes"] == 2500 * 2 * 16 * 128 * 2
    assert call["bytes"] * costs_looped.cache_layers(model) == \
        2500 * costs_looped.kv_bytes_per_token(model)
    assert call["flops"] / call["bytes"] == 1.0  # far under the ridge
    # a step of 19.9 GB of weights and ~4.3 GB of K/V: 29.6 ms at its roofline
    step = costs_looped.decode_weight_bytes(model) + 2760 * 1572864
    assert round(step / 819e9 * 1e3, 1) == 29.6
    assert costs_looped.passes({"n_layers": 16}) == 1


def _obs(model, events, numbers):
    trace = reduce_trace.Trace({0: events}, [])
    return {"reduction": reduce_trace.Reduction(trace, 0.0, 1.0),
            "model": model, "peaks": PEAKS, "numbers": numbers}


def _read(name, obs):
    cell = harness.load_cell(CELL)
    spec = next(m for m in cell.per_layer if m["name"] == name)["spec"]
    return harness.layer_reader(spec)(obs, spec["params"])


def test_the_readers_on_the_looped_cell():
    """Two decode steps of 192 kernel calls each, 2,500 attended tokens
    a step, the program's named ops and an unnamed weight prefetch in
    between, and one prefill execution: each reading worked by hand."""
    model = harness.load_cell(CELL).config["model"]
    tokens, steps = 2500.0, 100.0
    kv = costs_looped.kv_bytes_per_token(model) * tokens
    weights = costs_looped.decode_weight_bytes(model)
    numbers = {"counters.serving.paged_decode_steps": steps,
               "counters.serving.loop_kv_read_bytes": steps * kv,
               "facts.ctx_tokens_per_decode_step": tokens}
    per_call = 2 * tokens * 16 * 128 * 2 / 819e9
    dec, events, t = "jit(forward_decode_paged)", [], 0.0
    for _ in range(2):
        for _ in range(192):
            events.append(reduce_trace.Event(
                "paged_attn.1", dec + "/loop_pass/attention/paged_attn", t,
                t + 2 * per_call))
            t += 2 * per_call
            events.append(reduce_trace.Event(
                "fusion.2", dec + "/loop_pass/mlp/dot_general", t, t + 1e-4))
            t += 1e-4
            events.append(reduce_trace.Event("copy-done.3", "", t, t + 5e-5))
            # an asynchronous copy's event overlaps the next op without
            # nesting in it: its time is counted once
            events.append(reduce_trace.Event("copy-start.4", "", t + 2e-5,
                                             t + 7e-5))
            t += 5e-5
    decode_s = t + 2e-5  # the last copy-start ends after the last op
    events.append(reduce_trace.Event(
        "fusion.4", "jit(forward_prefill_paged)/loop_pass/dot_general", t,
        t + 0.01))
    events.append(reduce_trace.Event("copy-done.5", "", t + 0.01, t + 0.02))
    obs = _obs(model, events, numbers)
    assert _read("loop_kv_gb_per_step", obs) == pytest.approx(kv / 1e9)
    assert _read("paged_attn_roofline", obs) == pytest.approx(50.0)
    assert obs["notes"]["paged_attn_cost"] == "memory-bound"
    least = (weights + kv) / 819e9
    assert _read("loop_decode_roofline", obs) == pytest.approx(
        100.0 * 2 * least / decode_s)
    # a program without the counters, an untraced run, a model that
    # runs its layers once: nothing to read, and no exception
    for name in NEW - {"loop_kv_gb_per_step"}:
        assert _read(name, dict(obs, numbers={})) is None
        assert _read(name, dict(obs, reduction=None)) is None
        assert _read(name, dict(obs, model=dict(model, loop_passes=1))) \
            is None
    assert _read("loop_kv_gb_per_step", dict(obs, numbers={})) is None
