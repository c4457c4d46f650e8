"""The yardstick's arithmetic: costs, percentile, token counts."""

import json
import os

import pytest

from benchmarks import costs, measure

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def flagship():
    with open(os.path.join(HERE, "configs", "flagship-1b-train.json")) as f:
        return json.load(f)


# hand-worked: per layer 8*E*HD + 2*t*HD + 6*E*F = 33.55M + t*4096 +
# 75.50M, times 16 layers, plus 2*E*V = 134.2M, times 3
@pytest.mark.parametrize("t, gflop", [(1024, 5.84), (8192, 7.25),
                                      (16384, 8.86), (32768, 12.08)])
def test_train_flops_per_token(flagship, t, gflop):
    got = costs.train_flops_per_token(flagship["model"], t) / 1e9
    assert got == pytest.approx(gflop, abs=0.005)


def test_train_flops_match_the_programs_own(flagship):
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**flagship["model"])
    for t in (1024, 8192):
        assert costs.train_flops_per_token(flagship["model"], t) == \
            tfm.train_flops_per_token(cfg, t)
    assert costs.count_params(flagship["model"]) == tfm.count_params(cfg)


def test_attention_share(flagship):
    m = flagship["model"]
    assert costs.attention_flops_share(m, 1024) == pytest.approx(0.0345,
                                                                 abs=5e-4)
    assert costs.attention_flops_share(m, 8192) == pytest.approx(0.222,
                                                                 abs=1e-3)


def test_flash_costs_one_chip(flagship):
    traffic = {"B": 8, "T": 1024}
    fwd = costs.flash_fwd_cost(flagship["model"], traffic, flagship)
    # 16 layers x 2 matmuls x 2 FLOPs x B*H*T*T*D over the causal half
    assert fwd["flops"] == 16 * 2 * 2 * 8 * 16 * 1024 * 1024 * 128 / 2
    # q, k, v, o in bf16 and the f32 lse, per layer
    assert fwd["bytes"] == 16 * (4 * 8 * 1024 * 16 * 128 * 2
                                 + 8 * 16 * 1024 * 4)
    dkv = costs.flash_dkv_cost(flagship["model"], traffic, flagship)
    dq = costs.flash_dq_cost(flagship["model"], traffic, flagship)
    assert dkv["flops"] == 2 * fwd["flops"]
    assert dq["flops"] == 1.5 * fwd["flops"]
    # forward + the backward's dq, dk, dv, dp is what train_flops counts
    # for attention (3x forward); the kernels' own needs add s twice
    assert fwd["per"] == dkv["per"] == dq["per"] == "step"


def test_flash_fwd_cost_ring(flagship):
    config = dict(flagship, mesh={"dp": 1, "sp": 2, "tp": 2, "pp": 1,
                                  "ep": 1})
    ring = costs.flash_fwd_cost(flagship["model"], {"B": 2, "T": 8192},
                                config)
    # per device: blocks of [2, 4096, 8, 128]; rank 0 needs half a
    # block, rank 1 one and a half: one block on average, twice (remat)
    block = 2 * 2 * 2 * 8 * 4096 * 4096 * 128
    assert ring["flops"] == 2 * 16 * block


def test_paged_attn_cost_is_memory_bound(flagship):
    cost = costs.paged_attn_cost(flagship["model"], 1000.0)
    assert cost["bytes"] == 1000 * 16 * 128 * 2 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = costs.min_seconds(cost, peaks)
    assert bound == "memory"
    assert seconds == pytest.approx(cost["bytes"] / 819e9)


def test_context_tokens_read():
    # 3 generated: prefill gives the first, then steps read n+1, n+2
    assert costs.context_tokens_read(10, 3) == 11 + 12
    assert costs.context_tokens_read(10, 1) == 0


def test_percentile_is_nearest_rank():
    values = list(range(1, 11))
    assert measure.percentile(values, 90) == 9
    assert measure.percentile(values, 50) == 5
    assert measure.percentile(values, 100) == 10
    assert measure.percentile([7.5], 90) == 7.5
    assert measure.percentile([3, 1, 2], 1) == 1
    with pytest.raises(ValueError):
        measure.percentile([], 50)


def _rec(t_send, t_done, n_prompt, n_generated, **kw):
    return dict({"t_send": t_send, "t_done": t_done, "n_prompt": n_prompt,
                 "n_generated": n_generated, "status": 200, "error": None},
                **kw)


def test_serve_window_counts_requests_answered_inside_and_tokens_by_time():
    records = [
        _rec(0.0, 9.0, 100, 10),              # answered before the window
        _rec(8.0, 12.0, 100, 20),             # half of it inside: 60 tokens
        _rec(11.0, 15.0, 50, 40),             # whole: 90 tokens
        _rec(12.0, 16.0, 50, 10, status=503, error="refused"),
        _rec(19.0, 21.0, 100, 10),            # half of it inside: 55 tokens
    ]
    w = measure.serve_window(records, 10.0, 20.0)
    assert (w["attempted"], w["failed"], w["completed"]) == (3, 1, 2)
    assert w["prompt_tokens"] == 150 and w["generated_tokens"] == 60
    assert w["serve_tok_s"] == pytest.approx((60 + 90 + 55) / 10.0)
    # per second: 30 in 10-11, 52.5 in 11-12, 22.5 in 12-15, nothing in
    # 15-19, 55 in 19-20; the middle of the ten is between 22.5 and 22.5
    assert measure.slice_rates(records, 10.0, 20.0) == pytest.approx(
        [30, 52.5, 22.5, 22.5, 22.5, 0, 0, 0, 0, 55])
    assert w["median_second_tok_s"] == pytest.approx(22.5)
    # latencies per generated token: 4/20 = 0.2 and 4/40 = 0.1
    assert w["norm_lat_p90"] == pytest.approx(0.2)


def test_tokens_served_add_up_over_adjacent_windows():
    records = [_rec(1.0, 7.0, 30, 30), _rec(2.0, 3.0, 5, 5),
               _rec(6.5, 9.5, 10, 20)]
    whole = measure.tokens_served(records, 0.0, 10.0)
    assert whole == 60 + 10 + 30
    parts = sum(measure.tokens_served(records, a, a + 2.5)
                for a in (0.0, 2.5, 5.0, 7.5))
    assert parts == pytest.approx(whole)


def test_serve_tok_s_is_the_windows_mean_and_a_stall_lowers_it():
    # eight clients, one 100-token request a second each, for 20 s; then
    # the same with every request between 5 and 11 s taking twice as long
    steady = [_rec(float(t), t + 1.0, 50, 50) for t in range(20)
              for _ in range(8)]
    w = measure.serve_window(steady, 0.0, 20.0)
    assert w["serve_tok_s"] == w["median_second_tok_s"] == 800.0
    slowed = []
    for _ in range(8):
        t = 0.0
        while t < 20.0:
            d = 2.0 if 5.0 <= t < 11.0 else 1.0
            slowed.append(_rec(t, t + d, 50, 50))
            t += d
    w = measure.serve_window(slowed, 0.0, 20.0)
    # the reported number pays for the stall; the median second, which
    # is only logged, would have hidden it
    assert w["serve_tok_s"] == pytest.approx(680.0)
    assert w["median_second_tok_s"] == pytest.approx(800.0)


def test_a_failed_request_serves_nothing_and_counts_as_failed():
    records = [_rec(1.0, 2.0, 10, 10), _rec(1.0, 2.0, 10, 0),
               _rec(1.0, 3.0, 10, 10, status=0, error="reset")]
    w = measure.serve_window(records, 0.0, 4.0)
    assert (w["attempted"], w["failed"], w["completed"]) == (3, 2, 1)
    assert w["serve_tok_s"] == pytest.approx(20 / 4.0)


def test_flatten_and_delta():
    before = measure.flatten("counters", {"serving": {"steps": 3}}, {})
    after = measure.flatten("counters", {"serving": {"steps": 10, "x": 2},
                                         "note": "text"}, {})
    assert measure.delta(after, before) == {"counters.serving.steps": 7.0,
                                            "counters.serving.x": 2.0}
