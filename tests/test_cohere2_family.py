"""The MHA family widened (Command A+'s block: grouped-query heads,
sliding-window and full layers with a block table per layer type, a
parallel block under LayerNorm, a tied head, averaged shared experts
beside a held share of the routed ones) at a small size on the CPU,
seeded weights, against benchmarks/reference_cohere2_moe.py."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference_cohere2_moe as ref
from dmlc_tpu import telemetry
from dmlc_tpu.base import DMLCError
from dmlc_tpu.models import transformer as tfm
from dmlc_tpu.ops import dispatch
from dmlc_tpu.ops import flash_attention as flash
from dmlc_tpu.ops import paged_attention as paged
from dmlc_tpu.serving import InferenceEngine
from dmlc_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4   # block size of the small pools
W = 8    # the small sliding window: a ring of 8 / 4 + 1 = 3 blocks


def small(**over):
    """d 64, 8 query heads on 2 K/V heads of 16, window 8, layers
    sliding-sliding-sliding-full, 8 routed experts top-2 of which 4 are
    held, 2 shared experts averaged, float32."""
    fields = dict(
        vocab=128, d_model=64, n_heads=8, n_kv_heads=2, head_dim=16,
        n_layers=4, n_experts=4, dtype="float32", moe_topk=2,
        sliding_window=W, layer_group_size=4, full_layers_rope=False,
        rope_theta=50000.0, norm="layer", norm_eps=1e-5,
        parallel_block=True, tie_embeddings=True, logit_scale=1.0,
        moe_router="sigmoid", moe_n_routed=8, moe_held_start=2,
        moe_d_ff=32, moe_n_shared=2, moe_shared_average=True)
    fields.update(over)
    return tfm.TransformerConfig(**fields)


def spec_of(cfg):
    return ref.Spec(
        top_k=cfg.moe_topk, held_start=cfg.moe_held_start,
        window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        full_every=cfg.layer_group_size, layer_offset=cfg.layer_offset,
        norm_eps=cfg.norm_eps, logit_scale=cfg.logit_scale)


def weights(cfg, seed=0):
    """Seeded weights five times init_params' scale (norm weights drawn
    around 1), so that routing and attention are far from uniform."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def scaled(path, a):
        if path[-1].key.startswith("ln"):
            return 1 + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a * 5

    return jax.tree_util.tree_map_with_path(scaled, params)


def new_cache(cfg, n_blocks=32, rows=4):
    return PagedKVCache(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, n_blocks=n_blocks,
        block_size=BS, dtype=np.float32,
        pool_shapes=cfg.kv_pool_shapes(n_blocks, BS),
        sliding_shapes=cfg.sliding_pool_shapes(rows, BS),
        sliding_window=cfg.sliding_window)


def test_the_tree_and_the_pools_of_the_widened_family():
    cfg = small()
    assert cfg.family == "mha_swa" and cfg.served_only and not cfg.latent
    assert cfg.layer_kinds == ("sliding", "sliding", "sliding", "full")
    assert small(layer_offset=2).layer_kinds == (
        "sliding", "full", "sliding", "sliding")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "ln_f", "layers", "experts"}  # tied
    layer = params["layers"][0]
    assert set(layer) == {"ln1", "wq", "wk", "wv", "wo", "gate", "s_in",
                          "s_gate", "s_out"}  # parallel: one norm
    assert layer["wq"].shape == (64, 8, 16)
    assert layer["wk"].shape == layer["wv"].shape == (64, 2, 16)
    assert layer["gate"].shape == (64, 8) and layer["s_in"].shape == (64, 64)
    assert params["experts"]["w_in"].shape == (4 * 4, 64, 32)
    assert tfm.count_params(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    # the full layer's pool by n_blocks, the sliding layers' by rows
    assert cfg.kv_pool_shapes(32, BS) == ((1, 32, BS, 2, 16),) * 2
    assert cfg.sliding_pool_shapes(4, BS) == ((3, 4 * 3, BS, 2, 16),) * 2
    # the flagship's family is what it was: no sliding pool, H K/V heads
    plain = tfm.TransformerConfig()
    assert plain.family == "mha" and not plain.served_only
    assert plain.sliding_pool_shapes(4, BS) == ()
    assert plain.kv_pool_shapes(5, 4) == ((4, 5, 4, 4, 16),) * 2
    with pytest.raises(NotImplementedError, match="served only"):
        tfm.unsharded_loss(params, jnp.zeros((1, 8), jnp.int32),
                           jnp.zeros((1, 8), jnp.int32), cfg)


@pytest.fixture(scope="module")
def model():
    cfg = small()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(7), (52,), 0, cfg.vocab)
    want = np.asarray(ref.logits_at(params, ids, np.arange(52),
                                    spec=spec_of(cfg)))
    return cfg, params, np.asarray(ids), want


def _prefill(cfg, params, cache, seq, ids, n):
    """Prefill ``ids[:n]`` (padded to whole blocks) as sequence ``seq``
    through the cache manager's tables; returns the logits after it."""
    assert cache.allocate(seq, n)
    padded = n + (-n % BS)
    row = np.zeros((1, padded), np.int32)
    row[0, :n] = ids[:n]
    out = tfm.forward_prefill_paged_swa(
        params, jnp.asarray(row), jnp.array([n - 1]),
        *cache.device_pools(),
        jnp.asarray(cache.block_table(seq), jnp.int32),
        jnp.asarray(cache.sliding_prefill_ids(seq), jnp.int32), cfg)
    cache.adopt_device_pools(*out[1:5])
    cache.advance_many([(seq, n)])
    return np.asarray(out[0][0]), np.asarray(out[5])


def _decode(cfg, params, cache, seqs, tokens):
    """One decode step of the rows ``seqs`` consuming ``tokens``."""
    assert cache.extend_many(seqs, 1)
    tables, lengths = cache.block_tables_array(seqs)
    out = tfm.forward_decode_paged_swa(
        params, jnp.asarray(tokens, jnp.int32)[:, None],
        jnp.asarray(lengths)[:, None], *cache.device_pools(),
        jnp.asarray(tables), jnp.asarray(lengths),
        jnp.asarray(cache.sliding_tables_array(seqs)), cfg)
    cache.adopt_device_pools(*out[1:5])
    cache.advance_many([(s, 1) for s in seqs])
    return np.asarray(out[0][:, 0])


@pytest.mark.parametrize("n_prompt", [3, 8, 21, 33],
                         ids=["under_w", "at_w", "over_2w", "over_4w"])
def test_prefill_then_decode_through_the_cache_agree_with_the_reference(
        model, n_prompt):
    """Contexts under, at and several windows over W, decoded on past
    6 W through both tables.  5e-4: float32 against float32, whose only
    differences are the order of sums (the engine's experts accumulate
    per sorted tile, its attention per block) on logits of a few
    units."""
    cfg, params, ids, want = model
    cache = new_cache(cfg)
    logits, moe = _prefill(cfg, params, cache, 1, ids, n_prompt)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=5e-4)
    assert moe.shape == (4, 5) and (moe[:, -1] == n_prompt * 2).all()
    for at in range(n_prompt, 52):
        logits = _decode(cfg, params, cache, [1], [ids[at]])
        np.testing.assert_allclose(logits[0], want[at], atol=5e-4,
                                   err_msg=f"position {at}")
        assert len(cache._seq(1).ring) <= 3
    assert cache.stats()["sliding_blocks_in_use"] == min(
        3, cache.blocks_for(52))


def test_a_prompt_walked_in_chunks_of_rows_is_the_same_prompt(
        model, monkeypatch):
    cfg, params, ids, want = model
    whole, _ = _prefill(cfg, params, new_cache(cfg), 1, ids, 48)
    monkeypatch.setattr(tfm, "PREFILL_ROWS", 16)
    cache = new_cache(cfg)
    chunked, moe = _prefill(cfg, params, cache, 1, ids, 48)
    np.testing.assert_allclose(chunked, whole, atol=2e-5)
    np.testing.assert_allclose(chunked, want[47], atol=5e-4)
    assert (moe[:, -1] == 48 * 2).all()
    # and the cache it left decodes on
    logits = _decode(cfg, params, cache, [1], [ids[48]])
    np.testing.assert_allclose(logits[0], want[48], atol=5e-4)


def test_mixed_lengths_in_one_batch_through_the_engine(model):
    """Four requests of 3 to 30 prompt tokens in one batch of the
    engine, with a dead row: every generated token is the argmax of the
    reference's logits over the request's own context (teacher-forced,
    within 1e-3 of its top logit: float32 sums in another order)."""
    cfg, params, ids, _ = model
    before = dict(telemetry.counters_snapshot().get("serving", {}))
    eng = InferenceEngine(params, cfg, n_blocks=48, block_size=BS,
                          max_active=5, queue_depth=8)
    assert eng.cache.ring_blocks == 3 and eng.cache.n_sliding_blocks == 15
    prompts = [list(map(int, ids[a:a + n]))
               for a, n in ((0, 3), (5, 9), (11, 30), (2, 17))]
    eng.start()
    try:
        reqs = [eng.submit(p, 20) for p in prompts]
        for r in reqs:
            assert r.wait(300) and r.error is None, r.error
        stats = eng.cache.stats()
    finally:
        eng.close()
    assert stats["blocks_in_use"] == stats["sliding_blocks_in_use"] == 0
    for prompt, req in zip(prompts, reqs):
        out = list(req.generated)
        assert len(out) == 20 and req.preemptions == 0
        seq = np.asarray(prompt + out[:-1], np.int32)
        padded = np.pad(seq, (0, -len(seq) % 4))
        logits = np.asarray(ref.logits_at(
            params, padded, np.arange(len(prompt) - 1, len(seq)),
            spec=spec_of(cfg)))
        gaps = logits.max(-1) - logits[np.arange(20), out]
        assert gaps.max() < 1e-3, gaps
    c = telemetry.counters_snapshot()["serving"]
    grew = {k: c[k] - before.get(k, 0) for k in (
        "attn_full_ctx_tokens", "attn_sliding_ctx_tokens",
        "kv_sliding_blocks_released", "kv_block_steps",
        "kv_sliding_block_steps", "kv_cached_token_steps",
        "paged_decode_steps", "moe_pairs_total")}
    # every decode step of a row at length n: n + 1 keys in the full
    # layer, at most W in a sliding one
    full = sum(sum(range(len(p) + 1, len(p) + 20)) for p in prompts)
    sliding = sum(sum(min(n, W) for n in range(len(p) + 1, len(p) + 20))
                  for p in prompts)
    assert grew["attn_full_ctx_tokens"] == full
    assert grew["attn_sliding_ctx_tokens"] == sliding
    assert grew["kv_sliding_blocks_released"] > 0
    assert 0 < grew["kv_sliding_block_steps"] <= 15 * grew[
        "paged_decode_steps"]
    assert grew["kv_block_steps"] > grew["kv_sliding_block_steps"]
    assert grew["kv_cached_token_steps"] > 0 and grew["moe_pairs_total"] > 0
    gauges = telemetry.snapshot()["gauges"]["serving"]
    assert gauges["kv_sliding_blocks_total"] == 15


def test_speculation_is_refused_for_sliding_layers(monkeypatch):
    monkeypatch.setenv("DMLC_SERVE_SPEC_K", "2")
    cfg = small()
    with pytest.raises(ValueError, match="sliding"):
        InferenceEngine(weights(cfg), cfg, n_blocks=8, block_size=BS,
                        max_active=2)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Two chips of 4 experts each: the parts of the routed sum that
    the shares give, with the shared experts' mean counted once, are
    the uncut reference layer (all 8 experts held)."""
    whole = small(n_experts=8, moe_held_start=0, n_layers=1,
                  layer_group_size=1)
    params = weights(whole, seed=3)
    p = {**params["layers"][0], **params["experts"]}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64))
    want = ref._ffn(x[0], params["layers"][0], params["experts"], 0, 8,
                    lambda a: a, spec_of(whole))
    shared = tfm.swiglu_ffn(x, p["s_in"], p["s_gate"], p["s_out"],
                            tfm.ShardAxes()) / 2
    total, pairs = shared, 0
    for start in (0, 4):
        cfg = small(moe_held_start=start, n_layers=1, layer_group_size=1)
        mine = dict(p, **{n: p[n][start:start + 4]
                          for n in ("w_in", "w_gate", "w_out")})
        y, counts = tfm._moe_held_ffn(x, mine, cfg)
        total = total + (y - shared)
        pairs += int(np.asarray(counts)[:-1].sum())
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=5e-5)
    assert pairs == 24 * whole.moe_topk


# ---- the kernels against their lax twins, interpreted ------------------

@pytest.mark.parametrize("tq,tk,h,h_kv,span,off,bq,bk", [
    (64, 64, 4, 2, 0, None, 16, 16),     # grouped heads, causal
    (64, 64, 4, 2, 24, None, 16, 16),    # and a window
    (64, 64, 4, 1, 20, None, 16, 8),     # window not a block multiple
    (32, 96, 4, 2, 24, 64, 16, 16),      # a chunk of rows at an offset
    (32, 96, 4, 2, 0, 64, 16, 16),
    (40, 90, 2, 2, 17, 50, 16, 16),      # ragged, padded K/V
    (48, 48, 2, 1, 200, None, 16, 32),   # a window wider than the context
    # rows 70-79 see no key in their walk's FIRST block (keys 32-47),
    # whose running maximum must survive a block of masked scores
    (32, 96, 16, 1, 20, 64, 16, 16),
    (40, 90, 16, 1, 21, 50, 16, 16),     # a padded tail AND a window, group 16
    (32, 96, 16, 1, 20, 64, 16, 8),      # the window's edge inside a K/V block
], ids=["gqa", "gqa_w", "w_ragged", "offset_w", "offset", "padded",
        "wide_w", "first_step_unseen", "padded_w_g16", "edge_in_block"])
def test_flash_fwd_grouped_and_windowed_against_its_lax_twin(
        tq, tk, h, h_kv, span, off, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, tq, h, 128))
    k = jax.random.normal(ks[1], (1, tk, h_kv, 128))
    v = jax.random.normal(ks[2], (1, tk, h_kv, 128))
    want = flash.lax_attention(q, k, v, scale=128 ** -0.5, span=span,
                               q_offset=off)
    got = flash.flash_attention(q, k, v, span=span, q_offset=off,
                                block_q=bq, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    if h == h_kv and not span and off is None:
        return
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda q: flash.flash_attention(
            q, k, v, span=span, q_offset=off, block_q=bq, block_k=bk,
            interpret=True).sum())(q)


def _dense_attention(q, k_seq, v_seq, length, span):
    """q [S, H, D] at positions length..: softmax over the keys each
    sees, in numpy."""
    s_w, h, d = q.shape
    group = h // k_seq.shape[1]
    out = np.zeros((s_w, h, d), np.float32)
    for s in range(s_w):
        hi = length + s
        lo = max(0, hi - span + 1) if span else 0
        for head in range(h):
            sc = k_seq[lo:hi + 1, head // group] @ q[s, head] * d ** -0.5
            p = np.exp(sc - sc.max())
            out[s, head] = (p / p.sum()) @ v_seq[lo:hi + 1, head // group]
    return out


def _paged_case(lens, span, bs=16, h=16, h_kv=8, s_w=1, d=128):
    """Sequences of ``lens`` tokens laid out in a pool through a full
    table or, under ``span``, a ring table (logical block j at entry
    j mod R, taking over the block of j - R); garbage past each end."""
    rng = np.random.default_rng(0)
    ring = -(-span // bs) + 1 if span else 0
    k_pool = np.zeros((64, bs, h_kv, d), np.float32)
    v_pool = np.zeros_like(k_pool)
    free = list(rng.permutation(np.arange(1, 64)))
    width = ring or max(-(-(n + s_w) // bs) for n in lens) + 1
    tables = np.zeros((len(lens), width), np.int32)
    q = rng.standard_normal((len(lens), s_w, h, d)).astype(np.float32)
    want = np.zeros_like(q)
    for b, n in enumerate(lens):
        if not n:
            continue
        t = n + s_w
        k_seq = rng.standard_normal((t, h_kv, d)).astype(np.float32)
        v_seq = rng.standard_normal((t, h_kv, d)).astype(np.float32)
        for j in range(-(-t // bs)):
            at = j % ring if span else j
            if not (span and j >= ring):
                tables[b, at] = free.pop()
            page, m = tables[b, at], min(bs, t - j * bs)
            k_pool[page, :m] = k_seq[j * bs:j * bs + m]
            v_pool[page, :m] = v_seq[j * bs:j * bs + m]
            k_pool[page, m:] = 7.0
        want[b] = _dense_attention(q[b], k_seq, v_seq, n, span)
    return q, k_pool, v_pool, tables, np.asarray(lens, np.int32), want


@pytest.mark.parametrize("impl", ["lax", "pallas"])
@pytest.mark.parametrize("lens,span,kw", [
    ([5, 40, 0, 100], 0, {}),                        # a full table
    ([5, 31, 32, 33, 0, 100, 47, 48], 32, {}),       # under, at, over W
    ([200, 3], 32, {}),                              # far over W
    ([70, 1], 40, {}),                               # W off the block edge
    ([5, 40], 0, {"h": 8, "s_w": 3}),                # group 1, a window of 3
], ids=["full", "around_w", "far_over_w", "ragged_w", "mha_verify"])
def test_paged_decode_grouped_and_sliding_against_the_dense_answer(
        impl, lens, span, kw):
    """Grouped heads (16 on 8), a dead row, rows that cross a block
    edge, the ring table turned several times: kernel (interpreted) and
    lax twin both give dense attention over the keys in reach."""
    q, k_pool, v_pool, tables, lengths, want = _paged_case(lens, span, **kw)
    got = np.asarray(paged.paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lengths), impl=impl, span=span))
    live = lengths > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)


def test_a_verify_window_does_not_fit_a_ring():
    q, k_pool, v_pool, tables, lengths, _ = _paged_case([40], 32)
    with pytest.raises(ValueError, match="ring"):
        paged.paged_attention(
            jnp.zeros((1, 3, 16, 128)), jnp.asarray(k_pool),
            jnp.asarray(v_pool), jnp.asarray(tables), jnp.asarray(lengths),
            span=32)


# ---- the cache manager's two tables ------------------------------------

def test_a_sequence_never_holds_more_than_a_ring_of_sliding_blocks():
    cfg = small()
    cache = new_cache(cfg, n_blocks=16, rows=2)
    assert cache.ring_blocks == 3 and cache.n_sliding_blocks == 6
    before = telemetry.counters_snapshot().get("serving", {}).get(
        "kv_sliding_blocks_released", 0)
    assert cache.allocate(1, 5)                      # 2 blocks in each pool
    assert cache.block_table(1) == cache.sliding_prefill_ids(1) == [0, 1]
    cache.advance_many([(1, 5)])
    seen = set()
    for _ in range(3 * W + 6):                       # decoded past 3 W
        assert cache.extend(1, 1)
        cache.advance_many([(1, 1)])
        ring = cache.sliding_tables_array([1])[0]
        assert len(cache._seq(1).ring) <= 3
        seen.update(ring.tolist())
    assert cache.length(1) == 35 and len(cache.block_table(1)) == 9
    # the ring turned in place: 9 logical blocks through 3 physical ones
    assert seen == {0, 1, 2}
    released = telemetry.counters_snapshot()["serving"][
        "kv_sliding_blocks_released"] - before
    assert released == 9 - 3
    stats = cache.stats()
    assert stats["blocks_in_use"] == 9 and stats["n_blocks"] == 16
    assert stats["sliding_blocks_in_use"] == 3
    assert stats["sliding_blocks"] == 6 and stats["sliding_occupancy"] == 0.5
    assert stats["occupancy"] == 9 / 16  # the full layers' pool, as ever
    # a long prompt's prefill writes its last three blocks alone, in
    # logical order through the ring
    assert cache.allocate(2, 22)                     # 6 blocks, ring of 3
    assert len(cache.block_table(2)) == 6
    ring = cache.sliding_tables_array([2])[0].tolist()
    assert sorted(ring) == [3, 4, 5]
    assert cache.sliding_prefill_ids(2) == [ring[j % 3] for j in (3, 4, 5)]
    cache.free(1)
    assert cache.stats()["sliding_blocks_in_use"] == 3
    # released blocks are reused
    assert cache.allocate(3, 9)
    assert set(cache.sliding_tables_array([3])[0]) == {0, 1, 2}
    cache.free(2), cache.free(3)
    assert cache.stats()["sliding_blocks_in_use"] == 0
    assert cache.n_free_blocks == 16


def test_admission_fails_whole_when_either_pool_is_short():
    cfg = small()
    # full pool short: 4 blocks, a request of 5
    cache = new_cache(cfg, n_blocks=4, rows=2)
    assert not cache.fits_at_all(17) and cache.fits_at_all(16)
    assert cache.allocate(1, 9)                      # 3 of 4 full blocks
    assert not cache.can_reserve(5) and not cache.allocate(2, 5)
    assert cache.n_free_blocks == 1 and cache.n_free_sliding_blocks == 3
    # sliding pool short: one ring of 3, two rows want 2 each
    cache = PagedKVCache(
        4, 8, 16, n_blocks=16, block_size=BS,
        pool_shapes=cfg.kv_pool_shapes(16, BS),
        sliding_shapes=((3, 3, BS, 2, 16),) * 2, sliding_window=W)
    assert cache.allocate(1, 8) and cache.n_free_sliding_blocks == 1
    assert not cache.can_reserve(8) and not cache.allocate(2, 8)
    assert cache.n_free_blocks == 14                 # nothing was taken
    assert cache.can_reserve(4) and cache.allocate(2, 4)
    # decode: row 2 wants a second ring block, none is free: nothing
    # changes for either row
    cache.advance_many([(1, 8), (2, 4)])
    assert not cache.extend_many([1, 2], 1)
    assert len(cache.block_table(1)) == 2 and cache.n_free_blocks == 13
    assert not cache.extend(2, 1) and not cache.extend(1, 1)
    cache.free(2)
    assert cache.extend(1, 1) and cache.n_free_sliding_blocks == 0
    ring, blocks = list(cache._seq(1).ring), cache.block_table(1)
    cache.free(1)
    cache.free(1)                                    # idempotent by id
    for alloc, held in ((cache._ring_alloc, ring), (cache._alloc, blocks)):
        with pytest.raises(DMLCError, match="double free"):
            alloc.free(held)
    assert cache.n_free_sliding_blocks == 3 and cache.n_free_blocks == 16
    with pytest.raises(ValueError, match="go together"):
        PagedKVCache(4, 8, 16, sliding_window=W)


# ---- the configuration file --------------------------------------------

def _doc():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "command-a-plus-ep8-serve.json")) as f:
        return json.load(f)


def test_reference_constants_equal_the_configuration_file():
    doc = _doc()
    cfg = tfm.TransformerConfig(**doc["model"])
    assert ref.COMMAND_A_PLUS == spec_of(cfg) == ref.Spec(
        top_k=doc["num_experts_per_tok"],
        held_start=doc["model"]["moe_held_start"],
        window=doc["sliding_window"], rope_theta=doc["rope_theta"],
        full_every=doc["layer_switch"],
        layer_offset=doc["model"].get("layer_offset", 0),
        norm_eps=doc["layer_norm_eps"], logit_scale=doc["logit_scale"])
    published = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.moe_d_ff,
        "num_shared_experts": cfg.moe_n_shared,
        "num_experts_per_tok": cfg.moe_topk,
        "num_hidden_layers": cfg.n_layers,
        "num_experts": cfg.n_experts,                # held here
        "vocab_size": cfg.vocab, "sliding_window": cfg.sliding_window,
        "layer_switch": cfg.layer_group_size, "rope_theta": cfg.rope_theta,
        "layer_norm_eps": cfg.norm_eps, "logit_scale": cfg.logit_scale,
        "tie_word_embeddings": cfg.tie_embeddings,
        "use_parallel_block": cfg.parallel_block,
        "first_k_dense_replace": cfg.n_dense_layers}
    assert {k: doc[k] for k in published} == published
    assert doc["expert_selection_fn"] == cfg.moe_router == "sigmoid"
    assert doc["shared_expert_combination_strategy"] == "average" \
        and cfg.moe_shared_average and doc["norm_topk_prob"]
    assert cfg.norm == "layer" and not cfg.full_layers_rope
    assert cfg.moe_n_routed == doc["published"]["num_experts"] == 128
    assert cfg.moe_routed_scale == 1.0 and not cfg.moe_n_group
    assert sorted(doc["reduced"]) == sorted(
        k for k in doc["published"] if k != "parameters") == [
            "num_experts", "num_hidden_layers", "vocab_size"]
    assert doc["layer_types"][:cfg.n_layers] == [
        {"sliding": "sliding_attention", "full": "full_attention"}[k]
        for k in cfg.layer_kinds]
    assert doc["source"].endswith(
        "CohereLabs/command-a-plus-05-2026/blob/main/config.json")
    # the cut as ISSUE 33 reckons it: 4.73B parameters; a 32k sequence
    # holds 257 full blocks and a ring of 33
    assert round(tfm.count_params(cfg) / 1e9, 2) == 4.73
    sv = doc["serve"]
    full = cfg.kv_pool_shapes(sv["n_blocks"], sv["block_size"])
    sliding = cfg.sliding_pool_shapes(sv["max_active"], sv["block_size"])
    assert full == ((1, sv["n_blocks"], 128, 8, 128),) * 2
    assert sliding == ((3, 8 * 33, 128, 8, 128),) * 2
    assert "env" in sv and not sv["env"]


def test_the_family_lowers_for_the_tpu_at_published_widths():
    """What the chip will be asked: the prefill kernel at 128 query
    heads on 8 K/V heads with and without the window and a traced
    offset, the decode kernel over a full table and a ring."""
    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, 8192, 128, 128), bf)
    kv = jax.ShapeDtypeStruct((1, 32768, 8, 128), bf)
    off = jax.ShapeDtypeStruct((), jnp.int32)
    for span in (0, 4096):
        text = jax.jit(lambda q, k, v, off: flash.flash_attention(
            q, k, v, span=span, q_offset=off)).trace(q, kv, kv, off).lower(
                lowering_platforms=("tpu",)).as_text()
        assert text.count('kernel_name = "flash_fwd_o"') == 1
    assert paged.supports(128, 128, 8)
    pool = jax.ShapeDtypeStruct((3 * 264, 128, 8, 128), bf)
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        for width, span in ((257, 0), (33, 4096)):
            text = jax.jit(lambda q, k, v, t, n: paged.paged_attention(
                q, k, v, t, n, span=span)).trace(
                    jax.ShapeDtypeStruct((8, 1, 128, 128), bf), pool, pool,
                    jax.ShapeDtypeStruct((8, width), jnp.int32),
                    jax.ShapeDtypeStruct((8,), jnp.int32)).lower(
                        lowering_platforms=("tpu",)).as_text()
            assert text.count('kernel_name = "paged_attn"') == 1


def test_a_float32_stream_under_bf16_weights_stays_near_the_reference():
    """bf16 weights and matmul operands with the residual stream, the
    norms, the router's input and the logits in float32
    (``residual_dtype``): prefill and decode through the cache stay
    within 0.05 of the float32 reference's logits (bf16 operands move
    logits of a few units by about a hundredth), and the logits come
    out float32."""
    cfg = small(dtype="bfloat16", residual_dtype="float32")
    assert cfg.stream_dtype == jnp.float32 and cfg.jdtype == jnp.bfloat16
    params = weights(cfg)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (30,), 0,
                                        cfg.vocab))
    want = np.asarray(ref.logits_at(params, np.pad(ids, (0, 2)),
                                    np.arange(30), spec=spec_of(cfg)))
    cache = PagedKVCache(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, n_blocks=16,
        block_size=BS, dtype=jnp.bfloat16,
        pool_shapes=cfg.kv_pool_shapes(16, BS),
        sliding_shapes=cfg.sliding_pool_shapes(2, BS),
        sliding_window=cfg.sliding_window)
    logits, _ = _prefill(cfg, params, cache, 1, ids, 21)
    assert logits.dtype == np.float32
    worst = np.abs(logits - want[20]).max()
    for at in range(21, 30):
        logits = _decode(cfg, params, cache, [1], [ids[at]])
        worst = max(worst, np.abs(logits[0] - want[at]).max())
    assert worst < 0.05, worst
