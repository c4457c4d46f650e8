"""The hybrid family (Ling-3.0-flash's block: delta-rule linear
attention in most layers with its state in slots of the cache manager,
latent attention in the rest, group-limited routing over a held share
of the experts) at a small size on the CPU, seeded weights, against
benchmarks/reference_kda_mla_moe.py."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference_kda_mla_moe as ref
from dmlc_tpu import telemetry
from dmlc_tpu.models import transformer as tfm
from dmlc_tpu.ops import dispatch
from dmlc_tpu.ops import kda
from dmlc_tpu.serving import InferenceEngine
from dmlc_tpu.serving.kv_cache import PagedKVCache
from held_experts import check_case

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 8  # block size of the small pools


def small(**over):
    """d 64, 2 heads of 16, published layers 1-7 of a 2 KDA : 1 MLA
    pattern (5 KDA + 2 MLA), 1 dense + 6 expert layers, 16 experts in 4
    groups of which 2 stay, top-4, 4 held."""
    fields = dict(
        vocab=128, d_model=64, n_heads=2, head_dim=16, d_ff=128, n_layers=7,
        n_experts=4, dtype="float32", moe_topk=4, attention="kda_mla",
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=6e6, n_dense_layers=1,
        moe_router="sigmoid", moe_n_routed=16, moe_held_start=4,
        moe_d_ff=32, moe_n_shared=1, moe_routed_scale=2.5, moe_n_group=4,
        moe_topk_group=2, moe_router_bias=True, layer_group_size=3,
        layer_offset=1)
    fields.update(over)
    return tfm.TransformerConfig(**fields)


def spec_of(cfg):
    return ref.Spec(
        top_k=cfg.moe_topk, routed_scale=cfg.moe_routed_scale,
        held_start=cfg.moe_held_start, n_group=cfg.moe_n_group,
        topk_group=cfg.moe_topk_group, rope_theta=cfg.rope_theta,
        layer_group_size=cfg.layer_group_size,
        layer_offset=cfg.layer_offset,
        kda_lower_bound=cfg.kda_lower_bound)


_AS_SEEDED = {"ln1", "ln2", "ln_f", "o_norm", "kv_norm", "a_log", "dt_bias"}


def weights(cfg, seed=0):
    """Seeded weights five times init_params' scale (norms and the
    decay's own parameters as seeded), so that routing, the bias and
    attention are far from uniform."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in _AS_SEEDED else a * 5, params)


def empty_cache(cfg, n_blocks=16, n_slots=4):
    (pool,) = cfg.kv_pool_shapes(n_blocks, BS)
    return (jnp.zeros(pool, cfg.jdtype),) + tuple(
        jnp.zeros(shape, dt) for shape, dt in cfg.state_slot_shapes(n_slots))


def kda_inputs(seed, t, h, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (t, h, d)))
    v = jax.random.normal(ks[2], (t, h, d))
    g = -5.0 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    return q, k, v, g, beta


def test_the_tree_has_two_kinds_of_attention_group():
    cfg = small()
    assert cfg.latent and cfg.hybrid
    assert cfg.layer_kinds == ("kda", "mla", "kda", "kda", "mla", "kda",
                               "kda")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "unembed", "ln_f", "kda", "mla",
                           "dense", "blocks"}
    assert params["kda"]["w_qkv"].shape == (5, 64, 3 * 2 * 16)
    assert params["kda"]["conv"].shape == (5, 4, 96)
    assert params["kda"]["a_log"].dtype == jnp.float32
    assert params["mla"]["w_q"].shape == (2, 64, 2, 24)  # direct, no latent
    assert params["dense"]["w_in"].shape == (1, 64, 128)
    assert params["blocks"]["w_in"].shape == (1, 6, 4, 64, 32)
    assert params["blocks"]["gate"].shape == (1, 6, 64, 16)  # all routed
    assert params["blocks"]["gate_bias"].shape == (1, 6, 16)
    assert tfm.count_params(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    # the pool holds the MLA layers alone, the slots the KDA layers
    assert cfg.kv_pool_shapes(16, BS) == ((2, 16, 24, BS),)
    assert cfg.state_slot_shapes(4) == (
        ((5, 4, 2, 16, 16), "float32"), ((5, 4, 1, 288), "float32"))
    assert tfm.TransformerConfig().state_slot_shapes(4) == ()
    with pytest.raises(NotImplementedError):
        tfm.unsharded_loss(params, jnp.zeros((1, 8), jnp.int32),
                           jnp.zeros((1, 8), jnp.int32), cfg)


@pytest.mark.parametrize("t,real", [(37, 37), (150, 150), (192, 131)],
                         ids=["short", "ragged", "padded_tail"])
def test_chunked_prefill_against_the_recurrence(t, real):
    """T not a multiple of 64, and a padded tail (beta = 0, g = 0 past
    the last real token) that must leave the state where the last real
    token put it."""
    q, k, v, g, beta = kda_inputs(1, t, 2, 16)
    pad = jnp.arange(t) >= real
    g = jnp.where(pad[:, None, None], 0.0, g)
    beta = jnp.where(pad[:, None], 0.0, beta)
    o, s_t = kda.kda_chunk_scan(q, k, v, g, beta)
    want = ref.delta_rule(q[:real], k[:real], v[:real], jnp.exp(g[:real]),
                          beta[:real])
    np.testing.assert_allclose(np.asarray(o[:real]), np.asarray(want),
                               atol=2e-6)
    # the state after the padding is the state after the last real token
    _, s_real = kda.kda_chunk_scan(q[:real], k[:real], v[:real], g[:real],
                                   beta[:real])
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_real),
                               atol=1e-6)


def test_state_step_kernel_against_its_lax_form():
    """Interpreted, at the kernel's own tile sizes: live rows updated,
    a dead row's slot bit for bit as it was, live rows in any order."""
    b, h, d = 5, 8, 128
    q, k, v, g, beta = kda_inputs(2, b, h, d)
    state = jax.random.normal(jax.random.PRNGKey(3), (9, h, d, d))
    slots = jnp.array([3, 7, 1, 0, 5], jnp.int32)
    live = jnp.array([True, False, True, True, False])
    o_lax, s_lax = kda.kda_state_step(q, k, v, g, beta, state, slots, live,
                                      impl="lax")
    o_pl, s_pl = kda.kda_state_step(q, k, v, g, beta, state, slots, live,
                                    impl="pallas")
    np.testing.assert_allclose(np.asarray(o_pl)[np.asarray(live)],
                               np.asarray(o_lax)[np.asarray(live)],
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_lax),
                               atol=2e-6)
    untouched = [7, 5, 2, 4, 6, 8]  # the dead rows' slots and the free ones
    for got in (s_pl, s_lax):
        np.testing.assert_array_equal(np.asarray(got)[untouched],
                                      np.asarray(state)[untouched])
    # one step is the recurrence's step
    want = ref.delta_rule(q[:1], k[:1], v[:1], jnp.exp(g[:1]), beta[:1])
    zero = jnp.zeros_like(state)
    o0, _ = kda.kda_state_step(q, k, v, g, beta, zero, slots, live,
                               impl="pallas")
    np.testing.assert_allclose(np.asarray(o0[0]), np.asarray(want[0]),
                               atol=1e-6)


def test_state_step_kernel_with_no_live_row_keeps_every_slot():
    b, h, d = 2, 8, 128
    q, k, v, g, beta = kda_inputs(4, b, h, d)
    state = jax.random.normal(jax.random.PRNGKey(5), (3, h, d, d))
    _, got = kda.kda_state_step(q, k, v, g, beta, state,
                                jnp.array([1, 2], jnp.int32),
                                jnp.array([False, False]), impl="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(state))


@pytest.fixture(scope="module")
def model():
    cfg = small()
    params = weights(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32)
    want = [np.asarray(ref.logits_at(params, row, np.arange(64),
                                     spec=spec_of(cfg))) for row in ids]
    return cfg, params, ids, want


def test_prefill_logits_against_the_reference(model):
    cfg, params, ids, want = model
    blocks = np.array([3, 5, 7, 9], np.int32)
    logits, (pool, state, tails), (moe,) = tfm.prefill_paged(
        params, ids[:1, :32], np.array([28], np.int32), empty_cache(cfg),
        blocks, (np.array([2], np.int32),), cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), want[0][28], atol=5e-5)
    # only the sequence's own pages and its own slot were written
    written = np.flatnonzero(np.abs(np.asarray(pool)).sum(axis=(0, 2, 3)))
    assert written.tolist() == blocks.tolist()
    for a in (state, tails):
        used = np.flatnonzero(np.abs(np.asarray(a)).reshape(
            a.shape[0], a.shape[1], -1).sum(axis=(0, 2)))
        assert used.tolist() == [2]
    moe = np.asarray(moe)
    assert moe.shape == (6, cfg.n_experts + 2)
    assert (moe[:, -2] == 29 * cfg.moe_topk).all()  # pad tokens left out


@pytest.mark.parametrize("others", [False, True], ids=["alone", "in_a_batch"])
def test_prefill_then_decode_through_cache_and_slots(model, others):
    """Prefill of 24 tokens into slot 2 (padded to 32: the padding must
    not touch the state), then 40 teacher-forced decode steps against
    the reference's plain full forward; beside a dead row, or beside a
    second sequence in slot 0 whose logits are held to its own
    reference too (a slot leaks nothing)."""
    cfg, params, ids, want = model
    blocks = np.arange(1, 9, dtype=np.int32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :24] = ids[0, :24]
    logits, cache, _ = tfm.prefill_paged(
        params, padded, np.array([23], np.int32),
        empty_cache(cfg, n_blocks=24), blocks[:4], (np.array([2], np.int32),),
        cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), want[0][23], atol=5e-5)
    tables = np.zeros((3, 8), np.int32)
    tables[0] = blocks
    slots = np.array([2, 0, 0], np.int32)
    if others:
        logits, cache, _ = tfm.prefill_paged(
            params, ids[1:, :24], np.array([23], np.int32), cache,
            np.arange(9, 12, dtype=np.int32), (np.array([0], np.int32),), cfg)
        np.testing.assert_allclose(np.asarray(logits[0]), want[1][23],
                                   atol=5e-5)
        tables[1] = np.arange(9, 17)
    step = jax.jit(tfm.decode_paged, static_argnums=(7,))
    for t in range(24, 64):
        live = np.array([t, t if others else 0, 0], np.int32)
        logits, cache, (moe,) = step(
            params, np.array([[ids[0, t]], [ids[1, t]], [0]], np.int32),
            live[:, None], cache, tables, live, (slots,), cfg)
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[0][t],
                                   atol=1e-4)
        if others:
            np.testing.assert_allclose(np.asarray(logits[1, 0]), want[1][t],
                                       atol=1e-4)
        assert (np.asarray(moe)[:, -2] == (1 + others) * cfg.moe_topk).all()
    # dead rows carry slot 0: without a second sequence it stays zero
    if not others:
        assert not np.asarray(cache[1][:, 0]).any()
        assert not np.asarray(cache[2][:, 0]).any()


def _one_expert_layer(cfg, seed=4):
    params = weights(cfg, seed)
    p = jax.tree.map(lambda a: a[0, 0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 24, cfg.d_model))
    return p, x


def _reference_layer(x, p, cfg):
    return ref._experts(x[0], dict(p, ln2=jnp.ones(cfg.d_model)),
                        lambda a: a, spec_of(cfg))


def test_group_limited_selection_against_the_reference():
    cfg = small()
    p, x = _one_expert_layer(cfg)
    scores = jax.nn.sigmoid(x[0] @ p["gate"])
    got_s, got_i = tfm._group_limited_top_k(scores, p["gate_bias"], cfg)
    want_w, want_i = ref.route(scores, p["gate_bias"], spec_of(cfg))
    np.testing.assert_array_equal(np.sort(np.asarray(got_i), axis=-1),
                                  np.sort(np.asarray(want_i), axis=-1))
    # every pick lies in one of the 2 groups that stayed, the weights
    # are the unbiased scores, and the bias changed some token's picks
    assert (np.array([len(set(row // 4)) for row in np.asarray(got_i)])
            <= cfg.moe_topk_group).all()
    np.testing.assert_allclose(
        np.asarray(got_s), np.take_along_axis(np.asarray(scores),
                                              np.asarray(got_i), -1))
    unbiased_i = tfm._group_limited_top_k(scores, None, cfg)[1]
    assert (np.sort(np.asarray(unbiased_i)) != np.sort(np.asarray(got_i))
            ).any()
    np.testing.assert_allclose(
        np.sort(2.5 * np.asarray(got_s) / np.asarray(got_s).sum(
            -1, keepdims=True)), np.sort(np.asarray(want_w)), rtol=1e-6)


@pytest.mark.parametrize("case", ["rule", "overflow", "last_group"])
def test_the_held_experts_under_the_tile_rule(case, monkeypatch):
    """Group-limited top-k through the trip loop: the rule's one trip
    and forced 8-row trips, routing skewed onto the held routing group
    past the rule's tile (256 tokens put 1,024 pairs on a tile of 512),
    and the layer's groups last in a junk stack: the reference's expert
    layer every time."""
    cfg = small()
    p, x = _one_expert_layer(cfg, seed=7)
    if case == "overflow":
        x = jax.random.normal(jax.random.PRNGKey(8), (1, 256, cfg.d_model))
    check_case(case, p, x, cfg, _reference_layer, monkeypatch, atol=5e-5)


def test_the_shares_add_up():
    """The partial results of all four shares of one layer (a routing
    group each: experts [0,4), [4,8), [8,12), [12,16) of 16), the
    shared expert counted once, equal the uncut reference: a share
    that holds all 16."""
    whole = small(n_experts=16, moe_held_start=0)
    p, x = _one_expert_layer(whole, seed=6)
    want = _reference_layer(x, p, whole)
    shared = tfm.swiglu_ffn(x, p["s_in"], p["s_gate"], p["s_out"],
                            tfm.ShardAxes())
    total = shared
    pairs = 0
    for start in range(0, 16, 4):
        cfg = small(moe_held_start=start)
        mine = dict(p, **{name: p[name][start:start + 4]
                          for name in ("w_in", "w_gate", "w_out")})
        y, counts = tfm._moe_held_ffn(x, mine, cfg)
        np.testing.assert_allclose(
            np.asarray(y[0]), np.asarray(_reference_layer(x, mine, cfg)),
            atol=2e-5)
        total = total + (y - shared)
        pairs += int(np.asarray(counts)[:-2].sum())
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=5e-5)
    assert pairs == 24 * whole.moe_topk


def test_slots_bound_admission_beside_blocks():
    cfg = small()
    cache = PagedKVCache(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, n_blocks=16, block_size=BS,
        pool_shapes=cfg.kv_pool_shapes(16, BS),
        state_shapes=cfg.state_slot_shapes(2))
    assert cache.n_slots == 2 and cache.can_reserve(8)
    assert cache.allocate(1, 8) and cache.allocate(2, 8)
    assert cache.slot_ids([2, 1], pad_batch=3).tolist() == [1, 0, 0]
    # 14 blocks are free, no slot is: nothing more is admitted
    assert cache.n_free_blocks == 14 and not cache.can_reserve(8)
    assert not cache.allocate(3, 8) and cache.n_free_blocks == 14
    assert cache.stats()["state_slots_in_use"] == 2
    cache.free(1)
    assert cache.can_reserve(8) and cache.allocate(3, 8)
    assert cache.slot_ids([3]).tolist() == [0]  # the freed slot, reused
    pools = cache.device_pools()
    assert [p.shape for p in pools] == [
        (2, 16, 24, BS), (5, 2, 2, 16, 16), (5, 2, 1, 288)]
    # a cache without recurrent state never refuses for slots
    plain = PagedKVCache(2, 2, 16, n_blocks=4, block_size=BS)
    assert plain.n_slots == 0 and plain.can_reserve(8)
    assert "state_slots" in plain.stats()


def _generate_all(cfg, prompts, n_new, **engine):
    eng = InferenceEngine(weights(cfg), cfg, block_size=BS, queue_depth=8,
                          **engine)
    eng.start()
    try:
        reqs = [eng.submit(p, n_new) for p in prompts]
        for r in reqs:
            assert r.wait(120) and r.error is None, r.error
        assert eng.cache.stats()["state_slots_in_use"] == 0
        return [list(r.generated) for r in reqs], \
            sum(r.preemptions for r in reqs)
    finally:
        eng.close()


def test_preempt_then_resume_returns_the_same_ids():
    """A pool too small for both requests preempts one, which frees its
    slot; the resume re-prefills state and rows and goes on where it
    stopped: the same ids as with room for both."""
    cfg = small()
    prompts = [[(5 * i + j) % cfg.vocab for i in range(16)] for j in (1, 2)]
    before = telemetry.counters_snapshot().get("serving", {})
    roomy, none = _generate_all(cfg, prompts, 20, n_blocks=16, max_active=2)
    tight, some = _generate_all(cfg, prompts, 20, n_blocks=7, max_active=2)
    assert none == 0 and some > 0
    assert tight == roomy and all(len(g) == 20 for g in tight)
    c = telemetry.counters_snapshot()["serving"]
    grew = {k: c[k] - before.get(k, 0) for k in (
        "state_slot_allocs", "kda_state_rw_bytes", "paged_decode_steps",
        "moe_pairs_total")}
    assert grew["state_slot_allocs"] == 4 + some
    # 2 x 5 layers x [2, 16, 16] float32 a live row and step
    assert grew["kda_state_rw_bytes"] % (2 * 5 * 2 * 16 * 16 * 4) == 0
    assert 0 < grew["kda_state_rw_bytes"] <= (
        2 * 5 * 2 * 16 * 16 * 4) * 2 * grew["paged_decode_steps"]
    assert grew["moe_pairs_total"] > 0


def test_one_slot_serves_requests_one_after_another():
    cfg = small()
    prompts = [[(3 * i + j) % cfg.vocab for i in range(10)] for j in range(3)]
    apart, _ = _generate_all(cfg, prompts, 6, n_blocks=16, max_active=3)
    queued, _ = _generate_all(cfg, prompts, 6, n_blocks=16, max_active=1)
    assert queued == apart


def test_speculation_is_refused_for_recurrent_layers(monkeypatch):
    monkeypatch.setenv("DMLC_SERVE_SPEC_K", "2")
    cfg = small()
    with pytest.raises(ValueError, match="recurrent"):
        InferenceEngine(weights(cfg), cfg, n_blocks=8, block_size=BS,
                        max_active=2)


def test_the_reference_controls_move_the_logits(model):
    cfg, params, ids, want = model
    for control in ({"quantize": jnp.float8_e4m3fn},
                    {"state_dtype": jnp.bfloat16}):
        low = np.asarray(ref.logits_at(params, ids[0], np.arange(64),
                                       spec=spec_of(cfg), **control))
        assert np.abs(low - want[0]).max() > 1e-3, control


def test_reference_constants_equal_the_configuration_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling3-flash-ep8-serve.json")) as f:
        doc = json.load(f)
    cfg = tfm.TransformerConfig(**doc["model"])
    assert ref.LING3 == spec_of(cfg) == ref.Spec(
        top_k=doc["num_experts_per_tok"],
        routed_scale=doc["routed_scaling_factor"],
        held_start=doc["model"]["moe_held_start"], n_group=doc["n_group"],
        topk_group=doc["topk_group"], rope_theta=doc["rope_theta"],
        layer_group_size=doc["layer_group_size"],
        layer_offset=doc["model"]["layer_offset"],
        kda_lower_bound=doc["kda_lower_bound"])
    assert ref.RMS_EPS == doc["rms_norm_eps"]
    published = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "head_dim": cfg.head_dim, "intermediate_size": cfg.d_ff,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "moe_intermediate_size": cfg.moe_d_ff,
        "moe_shared_expert_intermediate_size": cfg.moe_d_ff,
        "num_shared_experts": cfg.moe_n_shared,
        "num_experts_per_tok": cfg.moe_topk,
        "first_k_dense_replace": cfg.n_dense_layers,
        "num_hidden_layers": cfg.n_layers,
        "num_experts": cfg.n_experts,               # held here
        "vocab_size": cfg.vocab, "n_group": cfg.moe_n_group,
        "topk_group": cfg.moe_topk_group,
        "moe_router_enable_expert_bias": cfg.moe_router_bias,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rope_theta": cfg.rope_theta,
        "layer_group_size": cfg.layer_group_size,
        "short_conv_kernel_size": cfg.kda_conv_size,
        "kda_lower_bound": cfg.kda_lower_bound}
    assert {k: doc[k] for k in published} == published
    assert doc["q_lora_rank"] is None and cfg.q_lora_rank == 0
    assert doc["qk_head_dim"] == cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    assert cfg.moe_n_routed == doc["published"]["num_experts"] == 512
    assert sorted(doc["reduced"]) == sorted(
        k for k in doc["published"] if k != "parameters")
    # the kept layers are published 1-13: MLA at published 5 and 11,
    # and no SwiGLU limit is set in any of them
    kept = range(cfg.layer_offset, cfg.layer_offset + cfg.n_layers)
    assert [j for j, kind in zip(kept, cfg.layer_kinds) if kind == "mla"] \
        == [5, 11]
    assert not any(doc["expert_swiglu_limit_list"][j]
                   or doc["share_expert_swiglu_limit_list"][j] for j in kept)
    # the cut as ISSUE 31 reckons it: 5.41B parameters; 2.1 MB of state
    # a row and KDA layer, 1.48 GB for 64 rows; 0.45 GB of latent pool
    assert round(tfm.count_params(cfg) / 1e9, 2) == 5.41
    sv = doc["serve"]
    (pool,) = cfg.kv_pool_shapes(sv["n_blocks"], sv["block_size"])
    (state, _), (tails, _) = cfg.state_slot_shapes(sv["max_active"])
    assert pool == (2, 1536, 576, 128) and np.prod(pool) * 2 == 452984832
    assert state == (11, 64, 32, 128, 128) and tails == (11, 64, 288, 128)
    assert np.prod(state) * 4 == 1476395008


def test_hybrid_kernels_lower_for_the_tpu_at_published_widths():
    """What the chip will be asked: the state step at 64 rows of 32
    heads of 128 x 128 over all 11 layers' slots as one run."""
    assert kda.state_step_supports(32, 128, 128)
    assert not kda.state_step_supports(4, 16, 16)
    f32 = jnp.float32
    vec = jax.ShapeDtypeStruct((64, 32, 128), f32)
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        text = jax.jit(kda.kda_state_step).trace(
            vec, vec, vec, vec, jax.ShapeDtypeStruct((64, 32), f32),
            jax.ShapeDtypeStruct((11 * 64, 32, 128, 128), f32),
            jax.ShapeDtypeStruct((64,), jnp.int32),
            jax.ShapeDtypeStruct((64,), jnp.bool_)).lower(
                lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "kda_state_step"') == 1
    assert "tpu_custom_call" in text
