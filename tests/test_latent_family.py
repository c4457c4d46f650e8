"""The latent family (A.X-K1's block: latent attention, a leading dense
layer, a dropless share of sigmoid-routed experts) at a small size on
the CPU, seeded weights, against benchmarks/reference_mla_moe.py."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference_mla_moe as ref
from dmlc_tpu import telemetry
from dmlc_tpu.models import transformer as tfm
from dmlc_tpu.ops import dispatch
from dmlc_tpu.ops import flash_attention as flash
from dmlc_tpu.ops import paged_attention as paged
from dmlc_tpu.parallel.ring_attention import ring_attention_reference
from dmlc_tpu.serving import InferenceEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 8  # block size of the small pools


def small(**over):
    """d 64, 4 heads, lora 32 / 16, 16 experts top-4 of which 4 are
    held, 1 dense + 2 expert layers."""
    fields = dict(
        vocab=128, d_model=64, n_heads=4, head_dim=24, d_ff=128, n_layers=3,
        n_experts=4, dtype="float32", moe_topk=4, attention="mla",
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_yarn_factor=32.0,
        rope_yarn_original=64, rope_yarn_mscale_all_dim=1.0,
        n_dense_layers=1, moe_router="sigmoid", moe_n_routed=16,
        moe_held_start=4, moe_d_ff=32, moe_n_shared=1, moe_routed_scale=2.5)
    fields.update(over)
    return tfm.TransformerConfig(**fields)


def spec_of(cfg):
    return ref.Spec(top_k=cfg.moe_topk, routed_scale=cfg.moe_routed_scale,
                    held_start=cfg.moe_held_start,
                    yarn_original=cfg.rope_yarn_original)


def weights(cfg, seed=0):
    """Seeded weights five times init_params' scale, so that routing
    and attention are far from uniform."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(lambda a: a * 5 if a.ndim > 1 else a, params)


def empty_pool(cfg, n_blocks=16):
    return jnp.zeros(cfg.kv_pool_shapes(n_blocks, BS)[0], cfg.jdtype)


@pytest.fixture(scope="module")
def model():
    cfg = small()
    params = weights(cfg)
    ids = np.random.default_rng(0).integers(0, cfg.vocab, (1, 32)).astype(
        np.int32)
    want = np.asarray(ref.logits_at(params, ids[0], np.arange(32),
                                    spec=spec_of(cfg)))
    return cfg, params, ids, want


def test_defaults_leave_the_mha_tree_as_it_is():
    cfg = tfm.TransformerConfig()
    assert not cfg.latent and cfg.moe_router == "softmax"
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "unembed", "ln_f", "blocks"}
    assert cfg.kv_pool_shapes(5, 4) == ((4, 5, 4, 4, 16),) * 2


def test_latent_tree_has_a_leading_dense_group_beside_the_expert_layers():
    cfg = small()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert params["dense"]["w_in"].shape == (1, 64, 128)
    assert params["blocks"]["w_in"].shape == (1, 2, 4, 64, 32)
    assert params["blocks"]["gate"].shape == (1, 2, 64, 16)  # all routed
    assert params["blocks"]["s_in"].shape == (1, 2, 64, 32)
    assert params["blocks"]["w_kvb"].shape == (1, 2, 16, 4, 32)
    assert tfm.count_params(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    assert cfg.kv_pool_shapes(16, BS) == ((3, 16, 24, BS),)
    with pytest.raises(NotImplementedError):
        tfm.unsharded_loss(params, jnp.zeros((1, 8), jnp.int32),
                           jnp.zeros((1, 8), jnp.int32), cfg)


def test_prefill_logits_against_the_reference(model):
    cfg, params, ids, want = model
    blocks = np.array([3, 5, 7, 9], np.int32)
    logits, pool, moe = tfm.forward_prefill_paged_mla(
        params, ids, np.array([28], np.int32), empty_pool(cfg), blocks, cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), want[28], atol=2e-5)
    # only the sequence's own pages were written
    written = np.flatnonzero(np.abs(np.asarray(pool)).sum(axis=(0, 2, 3)))
    assert written.tolist() == blocks.tolist()
    # the counts are of the 29 real tokens, pad tokens left out
    moe = np.asarray(moe)
    assert moe.shape == (2, cfg.n_experts + 1)
    assert (moe[:, -1] == 29 * cfg.moe_topk).all()
    assert (moe[:, :-1].sum(axis=1) <= 29 * cfg.moe_topk).all()


def test_prefill_then_decode_through_the_latent_paged_cache(model):
    """Prefill of 24 tokens, then eight teacher-forced decode steps of
    a two-row batch (one dead row), each against the reference's plain
    full forward."""
    cfg, params, ids, want = model
    blocks = np.array([3, 5, 7, 9], np.int32)
    logits, pool, _ = tfm.forward_prefill_paged_mla(
        params, ids[:, :24], np.array([23], np.int32), empty_pool(cfg),
        blocks[:3], cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), want[23], atol=2e-5)
    tables = np.zeros((2, 4), np.int32)
    tables[0] = blocks
    block0 = np.asarray(pool[:, 0]).copy()
    for t in range(24, 32):
        logits, pool, moe = tfm.forward_decode_paged_mla(
            params, np.array([[ids[0, t]], [0]], np.int32),
            np.array([[t], [0]], np.int32), pool, tables,
            np.array([t, 0], np.int32), cfg)
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[t],
                                   atol=2e-5)
        assert (np.asarray(moe)[:, -1] == cfg.moe_topk).all()  # one live row
    # the dead row (table all zeros) wrote nothing into block 0
    np.testing.assert_array_equal(np.asarray(pool[:, 0]), block0)


def test_absorbed_decode_against_the_naive_up_projected_one():
    """One layer's attention over the same cached rows: scores from
    q' = q_nope W_kvb,k^T against the latent, and from up-projected
    keys and values."""
    cfg = small()
    p = jax.tree.map(lambda a: a[0], weights(cfg)["dense"])
    rng = np.random.default_rng(1)
    xn = jnp.asarray(rng.standard_normal((2, 12, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    q_nope, q_pe, row = tfm._mla_project(xn, p, pos, cfg)
    rkv, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = tfm._mla_scale(cfg)
    # naive: the last token's query against up-projected K and V
    kv = jnp.einsum("btr,rhd->bthd", row[..., :rkv], p["w_kvb"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        row[:, :, None, rkv:], (2, 12, cfg.n_heads, 8))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)[:, -1:]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    naive = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                       kv[..., nope:])
    # absorbed: the same query against the rows themselves
    q_lat = tfm._mla_absorbed_queries(q_nope[:, -1:], q_pe[:, -1:], p, cfg)
    s = jnp.einsum("bqhr,bkr->bhqk", q_lat, row) * scale
    o_lat = jnp.einsum("bhqk,bkr->bqhr", jax.nn.softmax(s, -1),
                       row[..., :rkv])
    absorbed = jnp.einsum("bshr,rhd->bshd", o_lat, p["w_kvb"][..., nope:])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(naive),
                               atol=1e-5)


@pytest.mark.parametrize("shape", [(200, 2, 192, 128), (64, 3, 24, 16),
                                   (96, 2, 192, 128)])
def test_flash_forward_with_its_own_v_size_against_the_lax_twin(shape):
    t, h, d, dv = shape
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (1, t, h, d))
    k = jax.random.normal(keys[1], (1, t, h, d))
    v = jax.random.normal(keys[2], (1, t, h, dv))
    got = flash.flash_attention(q, k, v, causal=True, scale=0.11,
                                interpret=True, block_q=64, block_k=32)
    want = ring_attention_reference(q, k, v, causal=True, scale=0.11)
    assert got.shape == (1, t, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert flash.supports((1, t, h, d), (1, t, h, d), (1, t, h, dv)) == (
        d == 192)


@pytest.mark.parametrize("s_w", [1, 3])
def test_latent_paged_kernel_against_its_lax_twin(s_w):
    b, h, row, v_dim, bs, n_blocks, w = 3, 4, 192, 128, 128, 12, 3
    assert paged.latent_supports(row, v_dim, bs)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((b, s_w, h, row)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((n_blocks, row, bs)), jnp.float32)
    tables = jnp.asarray(rng.permutation(n_blocks)[:b * w].reshape(b, w),
                         jnp.int32)
    lengths = jnp.asarray([5, 0, 2 * bs + 17], jnp.int32)
    kw = dict(v_dim=v_dim, scale=0.07)
    want = paged.latent_paged_attention(q, pool, tables, lengths,
                                        impl="lax", **kw)
    got = paged.latent_paged_attention(q, pool, tables, lengths,
                                       impl="pallas", **kw)
    assert got.shape == (b, s_w, h, v_dim)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)


def _one_expert_layer(cfg, seed=4):
    """One expert layer's parameters and normed inputs [1, 24, E]
    (unit rms, so that the reference's own ln2 changes nothing)."""
    p = jax.tree.map(lambda a: a[0, 0], weights(cfg, seed)["blocks"])
    x = np.random.default_rng(seed).standard_normal((1, 24, cfg.d_model))
    x = x / np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True))
    return p, jnp.asarray(x, jnp.float32)


def _reference_layer(x, p, cfg, spec):
    """reference_mla_moe's expert layer on unit-rms x."""
    with jax.default_matmul_precision("highest"):
        return ref._experts(x[0], dict(p, ln2=jnp.ones_like(p["ln2"])),
                            lambda a: a, spec)


@pytest.mark.parametrize("case", ["seeded", "all_on_one", "none_held"])
def test_expert_layer_is_dropless(case):
    """No capacity: every pick on ONE held expert (24 x 4 pairs where a
    capacity of 1.25 would keep 30), none held, and a seeded routing
    all equal the reference's masked sum."""
    cfg = small()
    p, x = _one_expert_layer(cfg)
    if case == "all_on_one":
        # experts 4..7 are held; a router that only ever picks 5 (and
        # three absent experts) puts a pair of every token on it
        gate = np.full((cfg.d_model, 16), -1.0, np.float32)
        gate[:, [5, 0, 1, 2]] = 1.0
        p = dict(p, gate=jnp.asarray(gate) * jnp.sign(x[0, 0])[:, None])
        x = jnp.broadcast_to(jnp.abs(x[:, :1]) * jnp.sign(x[0, 0]), x.shape)
    if case == "none_held":
        cfg = small(moe_held_start=12, moe_n_routed=32)
        gate = np.zeros((cfg.d_model, 32), np.float32)
        gate[:, :4] = 1.0
        p = dict(p, gate=jnp.asarray(gate) * jnp.sign(x[0, 0])[:, None])
        x = jnp.broadcast_to(jnp.abs(x[:, :1]) * jnp.sign(x[0, 0]), x.shape)
    y, counts = tfm._moe_held_ffn(x, p, cfg)
    want = _reference_layer(x, p, cfg, spec_of(cfg))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-5)
    counts = np.asarray(counts)
    assert counts[-1] == 24 * cfg.moe_topk
    if case == "all_on_one":
        assert counts[:-1].tolist() == [0, 24, 0, 0]
    if case == "none_held":
        assert counts[:-1].sum() == 0
        shared = tfm.swiglu_ffn(x, p["s_in"], p["s_gate"], p["s_out"],
                                tfm.ShardAxes())
        np.testing.assert_allclose(np.asarray(y), np.asarray(shared),
                                   atol=1e-6)


def test_expert_layer_walks_more_than_one_tile(monkeypatch):
    """Tiles of 8 rows over 96 pairs: the data-dependent trip count and
    the groups cut at tile edges."""
    cfg = small()
    p, x = _one_expert_layer(cfg, seed=5)
    want, _ = tfm._moe_held_ffn(x, p, cfg)
    monkeypatch.setattr(tfm, "MOE_TILE", 8)
    got, _ = tfm._moe_held_ffn(x, p, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_shares_add_up():
    """The partial results of all four shares of one layer (experts
    [0,4), [4,8), [8,12), [12,16) of 16), the shared expert counted
    once, equal the uncut reference: a share that holds all 16."""
    whole = small(n_experts=16, moe_held_start=0)
    p, x = _one_expert_layer(whole, seed=6)
    want = _reference_layer(x, p, whole, spec_of(whole))
    shared = tfm.swiglu_ffn(x, p["s_in"], p["s_gate"], p["s_out"],
                            tfm.ShardAxes())
    total = shared
    pairs = 0
    for start in range(0, 16, 4):
        cfg = small(moe_held_start=start)
        mine = dict(p, **{name: p[name][start:start + 4]
                          for name in ("w_in", "w_gate", "w_out")})
        y, counts = tfm._moe_held_ffn(x, mine, cfg)
        # the program's share equals the reference's share ...
        np.testing.assert_allclose(
            np.asarray(y[0]),
            np.asarray(_reference_layer(x, mine, cfg, spec_of(cfg))),
            atol=2e-5)
        total = total + (y - shared)
        pairs += int(np.asarray(counts)[:-1].sum())
    # ... and the shares add up to the whole, every pair counted once
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=5e-5)
    assert pairs == 24 * whole.moe_topk


def test_the_same_request_twice_through_the_engine():
    cfg = small()
    before = telemetry.counters_snapshot().get("serving", {})
    eng = InferenceEngine(weights(cfg), cfg, n_blocks=16, block_size=BS,
                          max_active=4, queue_depth=8)
    eng.start()
    try:
        prompt = list(range(1, 20))
        first = eng.generate(prompt, 6)
        assert eng.generate(prompt, 6) == first and len(first) == 6
        assert eng.cache.device_pools()[0].shape == (3, 16, 24, BS)
    finally:
        eng.close()
    c = telemetry.counters_snapshot()["serving"]
    grew = {k: c[k] - before.get(k, 0) for k in c if k.startswith("moe_")}
    # 2 requests x (19 prompt + 5 decoded) tokens x 4 picks x 2 layers
    assert grew["moe_pairs_total"] == 2 * 24 * 4 * 2
    assert 0 < grew["moe_pairs_held"] < grew["moe_pairs_total"]
    assert grew["moe_expert_load_max"] >= grew["moe_expert_load_mean"] > 0


def test_the_reference_control_moves_the_logits(model):
    cfg, params, ids, want = model
    low = np.asarray(ref.logits_at(params, ids[0], np.arange(32),
                                   quantize=jnp.float8_e4m3fn,
                                   spec=spec_of(cfg)))
    assert np.abs(low - want).max() > 0.05


def test_reference_constants_equal_the_configuration_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "axk1-ep16-serve.json")) as f:
        doc = json.load(f)
    yarn = doc["rope_scaling"]
    assert ref.AXK1 == ref.Spec(
        top_k=doc["num_experts_per_tok"],
        routed_scale=doc["routed_scaling_factor"],
        held_start=doc["model"]["moe_held_start"],
        rope_theta=doc["rope_theta"], yarn_factor=yarn["factor"],
        yarn_original=yarn["original_max_position_embeddings"],
        yarn_beta_fast=yarn["beta_fast"], yarn_beta_slow=yarn["beta_slow"],
        yarn_mscale_all_dim=yarn["mscale_all_dim"])
    assert ref.RMS_EPS == doc["rms_norm_eps"]
    assert yarn["mscale"] == yarn["mscale_all_dim"]  # cos / sin unscaled
    # the program's fields say the same as the published keys beside them
    cfg = tfm.TransformerConfig(**doc["model"])
    assert spec_of(cfg) == ref.AXK1._replace(
        rope_theta=cfg.rope_theta, yarn_factor=cfg.rope_yarn_factor,
        yarn_beta_fast=cfg.rope_yarn_beta_fast,
        yarn_beta_slow=cfg.rope_yarn_beta_slow,
        yarn_mscale_all_dim=cfg.rope_yarn_mscale_all_dim)
    published = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "intermediate_size": cfg.d_ff, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_shared_experts": cfg.moe_n_shared,
        "num_experts_per_tok": cfg.moe_topk,
        "first_k_dense_replace": cfg.n_dense_layers,
        "num_hidden_layers": cfg.n_layers,
        "n_routed_experts": cfg.n_experts,          # held here
        "vocab_size": cfg.vocab,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rope_theta": cfg.rope_theta}
    assert {k: doc[k] for k in published} == published
    assert cfg.moe_n_routed == doc["published"]["n_routed_experts"] == 192
    assert sorted(doc["reduced"]) == sorted(
        k for k in doc["published"] if k != "parameters")
    # the cut as ISSUE 27 reckons it: 4.84B parameters, 8,064 B a token
    assert round(tfm.count_params(cfg) / 1e9, 2) == 4.84
    sv = doc["serve"]
    (shape,) = cfg.kv_pool_shapes(sv["n_blocks"], sv["block_size"])
    assert sv["n_blocks"] * sv["block_size"] == 131072
    assert np.prod(shape) * 2 // 131072 == 8064


def test_latent_kernels_lower_for_the_tpu_at_published_widths():
    """What the chip will be asked: the prefill kernel at qk 192 / v
    128 and the latent paged kernel at 576-wide rows in pages of 128."""
    def lower(fn, *avals):
        with dispatch.force_kernel_mode(dispatch.MOSAIC):
            return jax.jit(fn).trace(*avals).lower(
                lowering_platforms=("tpu",)).as_text()

    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, 8192, 64, 192), bf16)
    v = jax.ShapeDtypeStruct((1, 8192, 64, 128), bf16)
    assert flash.supports(q.shape, q.shape, v.shape)
    text = lower(lambda q, k, v: flash.flash_attention(q, k, v, scale=0.1),
                 q, q, v)
    assert "flash_fwd_o" in text and "tpu_custom_call" in text
    text = lower(
        lambda q, pool, t, n: paged.latent_paged_attention(
            q, pool, t, n, v_dim=512, scale=0.1),
        jax.ShapeDtypeStruct((8, 1, 64, 576), bf16),
        jax.ShapeDtypeStruct((7 * 1024, 576, 128), bf16),
        jax.ShapeDtypeStruct((8, 129), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.int32))
    assert "mla_paged_attn" in text
