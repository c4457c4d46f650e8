"""The MHA family widened once more (MiMo-V2's block: full layers of
few K/V heads beside sliding-window layers of more, each with a learned
per-head sink; keys wider than values, their pages lane-flat; RoPE on
part of a head at a theta per kind; the attention's output scaled; a
leading dense layer, then held experts routed by a biased top-k) at a
small size on the CPU, seeded weights, against
benchmarks/reference_mimo_v2.py."""

import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference_mimo_v2 as ref
from dmlc_tpu import telemetry
from dmlc_tpu.models import transformer as tfm
from dmlc_tpu.ops import dispatch
from dmlc_tpu.ops import flash_attention as flash
from dmlc_tpu.ops import paged_attention as paged
from dmlc_tpu.serving import InferenceEngine
from dmlc_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4   # block size of the small pools
W = 6    # the small window: a ring of ceil(6 / 4) + 1 = 3 blocks
PATTERN = "FSSFSS"


def small(**over):
    """d 64, 8 query heads; full layers on 2 K/V heads, sliding ones on
    4 with sinks; keys 24 wide (8 of them rotated) and values 16; layer
    0 dense, then 4 of 8 routed experts held, top-2 by a biased score;
    float32."""
    fields = dict(
        vocab=128, d_model=64, n_heads=8, n_kv_heads=2, swa_n_kv_heads=4,
        head_dim=24, v_head_dim=16, d_ff=96, n_layers=4, n_experts=4,
        dtype="float32", moe_topk=2, moe_router="sigmoid", moe_n_routed=8,
        moe_held_start=2, moe_d_ff=32, moe_router_bias=True,
        n_dense_layers=1, sliding_window=W, attn_pattern=PATTERN,
        rope_dim=8, rope_theta=1e6, swa_rope_theta=100.0, swa_sink=True,
        attn_value_scale=0.707, norm="rms", norm_eps=1e-5)
    fields.update(over)
    return tfm.TransformerConfig(**fields)


def spec_of(cfg):
    return ref.Spec(
        top_k=cfg.moe_topk, held_start=cfg.moe_held_start,
        window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        swa_rope_theta=cfg.swa_rope_theta or cfg.rope_theta,
        rope_dim=cfg.rope_dim, pattern=cfg.attn_pattern,
        layer_offset=cfg.layer_offset, norm_eps=cfg.norm_eps,
        value_scale=cfg.attn_value_scale)


def weights(cfg, seed=0):
    """Seeded weights five times init_params' scale (norm weights drawn
    around 1, sinks and biases as seeded), so that routing and
    attention are far from uniform."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def scaled(path, a):
        name = path[-1].key
        if name.startswith("ln"):
            return 1 + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a if name in ("sink", "gate_bias") else a * 5

    return jax.tree_util.tree_map_with_path(scaled, params)


def new_cache(cfg, n_blocks=32, rows=4):
    return PagedKVCache(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, n_blocks=n_blocks,
        block_size=BS, dtype=np.float32,
        pool_shapes=cfg.kv_pool_shapes(n_blocks, BS),
        sliding_shapes=cfg.sliding_pool_shapes(rows, BS),
        sliding_window=cfg.sliding_window)


def test_the_tree_and_the_pools_of_two_geometries():
    cfg = small()
    assert cfg.family == "mha_swa" and cfg.served_only and cfg.flat_pages
    assert cfg.layer_kinds == ("full", "sliding", "sliding", "full")
    assert small(layer_offset=2).layer_kinds == (
        "sliding", "full", "sliding", "sliding")
    assert cfg.kv_geometry("full") == (2, 24, 16)
    assert cfg.kv_geometry("sliding") == (4, 24, 16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "unembed", "ln_f", "layers", "experts"}
    dense, sliding, _, full = params["layers"]
    assert set(dense) == {"ln1", "ln2", "wq", "wk", "wv", "wo", "w_in",
                          "w_gate", "w_out"}
    assert dense["w_in"].shape == (64, 96) and dense["w_out"].shape == (96, 64)
    assert set(sliding) == {"ln1", "ln2", "wq", "wk", "wv", "wo", "gate",
                            "gate_bias", "sink"}
    assert "sink" not in full and full["wk"].shape == (64, 2, 24)
    assert sliding["wq"].shape == (64, 8, 24)
    assert sliding["wk"].shape == (64, 4, 24)
    assert sliding["wv"].shape == (64, 4, 16)
    assert sliding["wo"].shape == (8, 16, 64)
    assert sliding["sink"].dtype == jnp.float32
    assert 0 <= float(sliding["sink"].min()) <= float(
        sliding["sink"].max()) <= np.log(2 * W)
    assert sliding["gate_bias"].shape == (8,)
    # three expert layers hold 4 experts each; the dense layer none
    assert params["experts"]["w_in"].shape == (3 * 4, 64, 32)
    assert tfm.count_params(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    # lane-flat pages: the heads side by side, K and V each its width;
    # the full layers' pool by n_blocks, the sliding layers' by rows
    assert cfg.kv_pool_shapes(32, BS) == ((2, 32, BS, 48), (2, 32, BS, 32))
    assert cfg.sliding_pool_shapes(4, BS) == ((2, 12, BS, 96),
                                              (2, 12, BS, 64))
    # without a value width of its own the family keeps its pages
    plain = small(v_head_dim=0, swa_n_kv_heads=0)
    assert not plain.flat_pages
    assert plain.kv_pool_shapes(32, BS) == ((2, 32, BS, 2, 24),) * 2


@pytest.fixture(scope="module")
def model():
    cfg = small()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(7), (44,), 0, cfg.vocab)
    want = np.asarray(ref.logits_at(params, ids, np.arange(44),
                                    spec=spec_of(cfg)))
    return cfg, params, np.asarray(ids), want


def _prefill(cfg, params, cache, seq, ids, n):
    """Prefill ``ids[:n]`` (padded to whole blocks) as sequence ``seq``
    through the cache manager's tables; returns the logits after it."""
    assert cache.allocate(seq, n)
    padded = n + (-n % BS)
    row = np.zeros((1, padded), np.int32)
    row[0, :n] = ids[:n]
    logits, pools, (moe,) = tfm.prefill_paged(
        params, jnp.asarray(row), jnp.array([n - 1]), cache.device_pools(),
        jnp.asarray(cache.block_table(seq), jnp.int32),
        (jnp.asarray(cache.sliding_prefill_ids(seq), jnp.int32),), cfg)
    cache.adopt_device_pools(*pools)
    cache.advance_many([(seq, n)])
    return np.asarray(logits[0]), np.asarray(moe)


_decode_paged = jax.jit(tfm.decode_paged, static_argnums=(7,))


def _decode(cfg, params, cache, seqs, tokens):
    """One decode step of the rows ``seqs`` consuming ``tokens``, at
    one table width (one program for the whole walk)."""
    assert cache.extend_many(seqs, 1)
    tables, lengths = cache.block_tables_array(seqs, pad_width=12)
    logits, pools, _ = _decode_paged(
        params, jnp.asarray(tokens, jnp.int32)[:, None],
        jnp.asarray(lengths)[:, None], cache.device_pools(),
        jnp.asarray(tables), jnp.asarray(lengths),
        (jnp.asarray(cache.sliding_tables_array(seqs)),), cfg)
    cache.adopt_device_pools(*pools)
    cache.advance_many([(s, 1) for s in seqs])
    return np.asarray(logits[:, 0])


@pytest.mark.parametrize("n_prompt", [3, 6, 13, 29],
                         ids=["under_w", "at_w", "over_2w", "over_4w"])
def test_prefill_then_decode_through_the_cache_agree_with_the_reference(
        model, n_prompt):
    """Contexts under, at and several windows over W, decoded on past
    7 W through both tables and both geometries.  5e-4: float32 against
    float32, whose only differences are the order of sums on logits of
    a few units."""
    cfg, params, ids, want = model
    cache = new_cache(cfg)
    logits, moe = _prefill(cfg, params, cache, 1, ids, n_prompt)
    np.testing.assert_allclose(logits, want[n_prompt - 1], atol=5e-4)
    # three expert layers' counts: 4 held, then routed pairs, then calls
    assert moe.shape == (3, 6) and (moe[:, -2] == n_prompt * 2).all()
    for at in range(n_prompt, 44):
        logits = _decode(cfg, params, cache, [1], [ids[at]])
        np.testing.assert_allclose(logits[0], want[at], atol=5e-4,
                                   err_msg=f"position {at}")
        assert len(cache._seq(1).ring) <= 3


def test_a_prompt_walked_in_chunks_of_rows_is_the_same_prompt(
        model, monkeypatch):
    cfg, params, ids, want = model
    whole, _ = _prefill(cfg, params, new_cache(cfg), 1, ids, 32)
    monkeypatch.setattr(tfm, "PREFILL_ROWS", 8)
    cache = new_cache(cfg)
    chunked, moe = _prefill(cfg, params, cache, 1, ids, 32)
    np.testing.assert_allclose(chunked, whole, atol=2e-5)
    np.testing.assert_allclose(chunked, want[31], atol=5e-4)
    assert (moe[:, -2] == 32 * 2).all()
    logits = _decode(cfg, params, cache, [1], [ids[32]])
    np.testing.assert_allclose(logits[0], want[32], atol=5e-4)


@pytest.mark.parametrize("change", ["no_sink", "one_theta", "whole_rope",
                                    "no_value_scale"])
def test_each_reading_moves_the_logits(model, change):
    """The reference that leaves out the sink, rotates every layer at
    one theta, rotates the whole head or drops the value scale is a
    different model at these weights: the engine's agreement above is
    not an accident of a part that does nothing."""
    cfg, params, ids, want = model
    spec = spec_of(cfg)
    if change == "no_sink":
        params = dict(params, layers=[{k: v for k, v in p.items()
                                       if k != "sink"}
                                      for p in params["layers"]])
    else:
        spec = spec._replace(**{
            "one_theta": {"swa_rope_theta": cfg.rope_theta},
            "whole_rope": {"rope_dim": cfg.head_dim},
            "no_value_scale": {"value_scale": 1.0}}[change])
    other = np.asarray(ref.logits_at(params, ids, np.arange(44), spec=spec))
    assert np.abs(other - want).max() > 0.05


def test_mixed_lengths_in_one_batch_through_the_engine(model):
    """Four requests of 3 to 25 prompt tokens in one batch of the
    engine, with a dead row: every generated token is the argmax of the
    reference's logits over the request's own context (teacher-forced,
    within 1e-3 of its top logit), and the decode steps' K/V reads are
    the pages of the keys each row attends, by each pool's geometry."""
    cfg, params, ids, _ = model
    before = dict(telemetry.counters_snapshot().get("serving", {}))
    eng = InferenceEngine(params, cfg, n_blocks=48, block_size=BS,
                          max_active=5, queue_depth=8)
    assert eng.cache.ring_blocks == 3 and eng.cache.n_sliding_blocks == 15
    prompts = [list(map(int, ids[a:a + n]))
               for a, n in ((0, 3), (5, 9), (11, 25), (2, 17))]
    eng.start()
    try:
        reqs = [eng.submit(p, 12) for p in prompts]
        for r in reqs:
            assert r.wait(300) and r.error is None, r.error
    finally:
        eng.close()
    for prompt, req in zip(prompts, reqs):
        out = list(req.generated)
        assert len(out) == 12 and req.preemptions == 0
        seq = np.asarray(prompt + out[:-1], np.int32)
        padded = np.pad(seq, (0, -len(seq) % 4))
        logits = np.asarray(ref.logits_at(
            params, padded, np.arange(len(prompt) - 1, len(seq)),
            spec=spec_of(cfg)))
        gaps = logits.max(-1) - logits[np.arange(12), out]
        assert gaps.max() < 1e-3, gaps
    c = telemetry.counters_snapshot()["serving"]
    grew = {k: c[k] - before.get(k, 0) for k in (
        "attn_kv_read_bytes", "attn_full_ctx_tokens", "paged_decode_steps")}
    # each decode step of a row at length n reads the pages of positions
    # [0, n] in both full layers (a page of 2 heads x (24 + 16) x 4 slots
    # x 4 B each) and those from the window's oldest key on in both
    # sliding ones (4 heads)
    full_page, ring_page = 2 * 2 * 40 * BS * 4, 2 * 4 * 40 * BS * 4
    want = 0
    for p in prompts:
        for n in range(len(p), len(p) + 11):
            reach = -(-(n + 1) // BS)
            want += full_page * reach + ring_page * (
                reach - max(n - (W - 1), 0) // BS)
    assert grew["attn_kv_read_bytes"] == want
    assert grew["attn_full_ctx_tokens"] == sum(
        sum(range(len(p) + 1, len(p) + 12)) for p in prompts)


def test_every_model_counts_its_attention_reads():
    """The counter is registered at 0 for a model of any family, and the
    flagship's pages are read whole: two pools of [bs, H, hd]."""
    from dmlc_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    before = telemetry.counters_snapshot().get("serving", {}).get(
        "attn_kv_read_bytes", 0)
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=BS,
                          max_active=2, queue_depth=4)
    assert eng._kv_page_bytes == (2 * 4 * BS * 4 * 16 * 4, 0)
    eng.start()
    try:
        req = eng.submit([1, 2, 3, 4, 5], 3)
        assert req.wait(300) and req.error is None
    finally:
        eng.close()
    grew = telemetry.counters_snapshot()["serving"][
        "attn_kv_read_bytes"] - before
    # two decode steps at lengths 5 and 6: two pages each
    assert grew == 2 * 2 * (2 * 4 * BS * 4 * 16 * 4)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen chips of one expert each: the parts of the routed sum
    that the shares give are the uncut reference layer (all 16 experts
    held), the biased top-2 picked the same on every chip."""
    whole = small(n_experts=16, moe_n_routed=16, moe_held_start=0,
                  n_layers=2, attn_pattern="FS")
    params = weights(whole, seed=3)
    layer = params["layers"][1]
    p = {**layer, **params["experts"]}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64))
    want = ref._ffn(x[0], layer, params["experts"], 0, 16, lambda a: a,
                    spec_of(whole))
    total, pairs = 0.0, 0
    for start in range(16):
        cfg = small(n_experts=1, moe_n_routed=16, moe_held_start=start,
                    n_layers=2, attn_pattern="FS")
        mine = dict(p, **{n: p[n][start:start + 1]
                          for n in ("w_in", "w_gate", "w_out")})
        y, counts = tfm._moe_held_ffn(x, mine, cfg)
        total = total + y
        pairs += int(np.asarray(counts)[:-2].sum())
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=5e-5)
    assert pairs == 24 * whole.moe_topk


# ---- the kernels against their lax twins, interpreted ------------------

def _dense(q, k_seq, v_seq, length, span, sink):
    """q [S, H, dk] at positions length..: softmax over the keys each
    sees, a sink logit beside them, in numpy."""
    s_w, h, _ = q.shape
    group = h // k_seq.shape[1]
    out = np.zeros((s_w, h, v_seq.shape[-1]), np.float64)
    for s in range(s_w):
        hi = length + s
        lo = max(0, hi - span + 1) if span else 0
        for head in range(h):
            sc = k_seq[lo:hi + 1, head // group] @ q[s, head] \
                * q.shape[-1] ** -0.5
            top = max(sc.max(), sink[head]) if sink is not None else sc.max()
            p = np.exp(sc - top)
            den = p.sum() + (np.exp(sink[head] - top) if sink is not None
                             else 0.0)
            out[s, head] = (p / den) @ v_seq[lo:hi + 1, head // group]
    return out


def _flat_case(lens, span, bs=16, h=16, h_kv=4, dk=48, dv=32, s_w=1):
    """Sequences of ``lens`` tokens in lane-flat pools through a full
    table or, under ``span``, a ring table; garbage past each end."""
    rng = np.random.default_rng(0)
    ring = -(-span // bs) + 1 if span else 0
    k_pool = np.zeros((64, bs, h_kv * dk), np.float32)
    v_pool = np.zeros((64, bs, h_kv * dv), np.float32)
    free = list(rng.permutation(np.arange(1, 64)))
    width = ring or max(-(-(n + s_w) // bs) for n in lens) + 1
    tables = np.zeros((len(lens), width), np.int32)
    q = rng.standard_normal((len(lens), s_w, h, dk)).astype(np.float32)
    sink = rng.uniform(0.0, 4.0, h).astype(np.float32)
    seqs = []
    for b, n in enumerate(lens):
        t = n + s_w
        k_seq = rng.standard_normal((t, h_kv, dk)).astype(np.float32)
        v_seq = rng.standard_normal((t, h_kv, dv)).astype(np.float32)
        seqs.append((k_seq, v_seq))
        if not n:
            continue
        for j in range(-(-t // bs)):
            at = j % ring if span else j
            if not (span and j >= ring):
                tables[b, at] = free.pop()
            page, m = tables[b, at], min(bs, t - j * bs)
            k_pool[page, :m] = k_seq[j * bs:j * bs + m].reshape(m, -1)
            v_pool[page, :m] = v_seq[j * bs:j * bs + m].reshape(m, -1)
            k_pool[page, m:] = 7.0
    return q, k_pool, v_pool, tables, np.asarray(lens, np.int32), sink, seqs


@pytest.mark.parametrize("impl", ["lax", "pallas"])
@pytest.mark.parametrize("lens,span,sunk,kw", [
    ([5, 40, 0, 100], 0, False, {}),                 # a full table
    ([5, 15, 16, 17, 0, 100, 47], 16, True, {}),     # under, at, over W
    ([200, 3], 16, True, {"h_kv": 8, "dk": 32}),     # far over W, 2:1
    ([70, 1], 20, True, {}),                         # W off the block edge
    ([5, 40], 0, True, {"s_w": 3}),                  # a window of 3
], ids=["full", "around_w", "far_over_w", "ragged_w", "verify"])
def test_paged_decode_of_lane_flat_pages_against_the_dense_answer(
        impl, lens, span, sunk, kw):
    """Keys wider than values in lane-flat pages, grouped heads (16 on
    4 or 8), a sink, a dead row, the ring turned several times: kernel
    (interpreted) and lax twin both give dense attention over the keys
    in reach, the sink in the denominator."""
    q, k_pool, v_pool, tables, lengths, sink, seqs = _flat_case(
        lens, span, **kw)
    got = np.asarray(paged.paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lengths), impl=impl, span=span,
        sink=jnp.asarray(sink) if sunk else None))
    for b, n in enumerate(lens):
        if n:
            want = _dense(q[b], *seqs[b], n, span, sink if sunk else None)
            np.testing.assert_allclose(got[b], want, atol=2e-5)
        elif impl == "pallas":
            assert (got[b] == 0).all()  # a dead row writes zeros


@pytest.mark.parametrize("tq,tk,h,h_kv,span,off,bq,bk", [
    (64, 64, 16, 1, 16, None, 16, 16),   # 16:1, a window, a sink
    (64, 64, 16, 2, 16, None, 16, 16),   # 8:1
    (64, 64, 16, 1, 0, None, 16, 16),    # a full layer's causal mask
    (32, 96, 16, 2, 20, 64, 16, 16),     # a chunk of rows at an offset
    (40, 90, 8, 1, 21, 50, 16, 16),      # a padded tail and a window
], ids=["g16_w", "g8_w", "g16_full", "offset_w", "padded_w"])
def test_flash_fwd_with_a_sink_against_its_lax_twin(
        tq, tk, h, h_kv, span, off, bq, bk):
    """qk 48 / v 32 (the published 192 / 128 cut), grouped heads at the
    published groups, a window, a query offset and a padded tail: the
    kernel (interpreted) puts each head's sink in the denominator as
    the lax twin does, and a call with a sink is forward-only."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, tq, h, 48))
    k = jax.random.normal(ks[1], (1, tk, h_kv, 48))
    v = jax.random.normal(ks[2], (1, tk, h_kv, 32))
    sink = jax.random.uniform(ks[3], (h,), jnp.float32, 0.0, 4.0)
    want = flash.lax_attention(q, k, v, scale=48 ** -0.5, span=span,
                               q_offset=off, sink=sink)
    got = flash.flash_attention(q, k, v, span=span, q_offset=off,
                                block_q=bq, block_k=bk, interpret=True,
                                sink=sink)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    plain = flash.lax_attention(q, k, v, scale=48 ** -0.5, span=span,
                                q_offset=off)
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-2
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda q: flash.flash_attention(
            q, k, v, span=span, q_offset=off, block_q=bq, block_k=bk,
            interpret=True, sink=sink).sum())(q)


# ---- what the chip will be asked ----------------------------------------

def test_the_family_lowers_for_the_tpu_at_published_widths():
    """The prefill kernel at 64 query heads on 4 and on 8 K/V heads, qk
    192 / v 128, with the window and a sink and with neither; the decode
    kernel over lane-flat pages of 4 heads through a full table and of
    8 through a ring, with the sink."""
    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, 8192, 64, 192), bf)
    off = jax.ShapeDtypeStruct((), jnp.int32)
    sink = jax.ShapeDtypeStruct((64,), jnp.float32)
    for h_kv, span in ((4, 0), (8, 128)):
        k = jax.ShapeDtypeStruct((1, 16384, h_kv, 192), bf)
        v = jax.ShapeDtypeStruct((1, 16384, h_kv, 128), bf)
        text = jax.jit(lambda q, k, v, off, sink: flash.flash_attention(
            q, k, v, span=span, q_offset=off,
            sink=sink if span else None)).trace(
                q, k, v, off, sink).lower(
                    lowering_platforms=("tpu",)).as_text()
        assert text.count('kernel_name = "flash_fwd_o"') == 1
    assert paged.flat_supports(4 * 192, 4 * 128, 128)
    assert not paged.supports(192, 128, 4)
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        for h_kv, width, span in ((4, 136, 0), (8, 2, 128)):
            pool_k = jax.ShapeDtypeStruct((640, 128, h_kv * 192), bf)
            pool_v = jax.ShapeDtypeStruct((640, 128, h_kv * 128), bf)
            text = jax.jit(lambda q, k, v, t, n, s: paged.paged_attention(
                q, k, v, t, n, span=span, sink=s if span else None)).trace(
                    jax.ShapeDtypeStruct((64, 1, 64, 192), bf), pool_k,
                    pool_v, jax.ShapeDtypeStruct((64, width), jnp.int32),
                    jax.ShapeDtypeStruct((64,), jnp.int32), sink).lower(
                        lowering_platforms=("tpu",)).as_text()
            assert text.count('kernel_name = "paged_attn"') == 1


#: sha256 (first 16 hex digits) of each Mosaic kernel's backend config
#: (its serialized body and compiler settings) as the flagship's and
#: Command A+'s calls lowered for the TPU BEFORE sinks and lane-flat
#: pages came into the kernels, source locations left out: a call
#: without a sink, at equal K and V widths, must lower to these bodies
_BODIES_BEFORE = {
    "paged_flagship": ["18b23a7a46947024"],
    "paged_cmdaplus_0": ["3415df9984a86d49"],
    "paged_cmdaplus_4096": ["86ade12ab534895b"],
    "flash_cmdaplus_0": ["3133b9d1ba3d91ed"],
    "flash_cmdaplus_4096": ["e0c758a2ebf697b2"],
    "flash_flagship": ["150a20d90543eb52"],
    "flash_flagship_grad": ["150a20d90543eb52", "4ae9eac12397b91b"],
}


def test_the_other_families_kernel_calls_lower_to_the_same_bodies():
    """The flagship's decode and train kernels and Command A+'s prefill
    and decode kernels, which the sink and the lane-flat pages share
    code with, lower to the Mosaic bodies they had before."""
    bf = jnp.bfloat16
    S = jax.ShapeDtypeStruct

    def bodies(f, *args):
        text = jax.jit(f).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        return [hashlib.sha256(m.encode()).hexdigest()[:16]
                for m in re.findall(r'backend_config = "([^"]*)"', text)]

    got = {}
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        with dispatch.force_kernel_mode(dispatch.MOSAIC):
            pool = S((16 * 64, 16, 16, 128), bf)
            got["paged_flagship"] = bodies(
                lambda q, k, v, t, n: paged.paged_attention(q, k, v, t, n),
                S((8, 1, 16, 128), bf), pool, pool, S((8, 9), jnp.int32),
                S((8,), jnp.int32))
            pool = S((3 * 264, 128, 8, 128), bf)
            for w, span in ((257, 0), (33, 4096)):
                got[f"paged_cmdaplus_{span}"] = bodies(
                    lambda q, k, v, t, n: paged.paged_attention(
                        q, k, v, t, n, span=span),
                    S((8, 1, 128, 128), bf), pool, pool,
                    S((8, w), jnp.int32), S((8,), jnp.int32))
            q = S((1, 8192, 128, 128), bf)
            kv = S((1, 32768, 8, 128), bf)
            for span in (0, 4096):
                got[f"flash_cmdaplus_{span}"] = bodies(
                    lambda q, k, v, o: flash.flash_attention(
                        q, k, v, span=span, q_offset=o),
                    q, kv, kv, S((), jnp.int32))
            q = S((1, 2048, 16, 128), bf)
            got["flash_flagship"] = bodies(
                lambda q, k, v: flash.flash_attention(q, k, v), q, q, q)
            got["flash_flagship_grad"] = bodies(jax.grad(
                lambda q, k, v: flash.flash_attention(q, k, v).astype(
                    jnp.float32).sum()), q, q, q)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert got == _BODIES_BEFORE


# ---- the configuration file --------------------------------------------

def _doc():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mimo-v2.5-ep16-serve.json")) as f:
        return json.load(f)


def test_reference_constants_equal_the_configuration_file():
    doc = _doc()
    cfg = tfm.TransformerConfig(**doc["model"])
    assert ref.MIMO_V2_5 == spec_of(cfg) == ref.Spec(
        top_k=doc["num_experts_per_tok"],
        held_start=doc["model"]["moe_held_start"],
        window=doc["sliding_window"], rope_theta=doc["rope_theta"],
        swa_rope_theta=doc["swa_rope_theta"],
        rope_dim=int(doc["partial_rotary_factor"] * doc["head_dim"]),
        pattern="".join("FS"[j] for j in doc["hybrid_layer_pattern"]),
        layer_offset=doc["model"].get("layer_offset", 0),
        norm_eps=doc["layernorm_epsilon"],
        value_scale=doc["attention_value_scale"])
    published = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "swa_num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_geometry("full")[0],
        "swa_num_key_value_heads": cfg.kv_geometry("sliding")[0],
        "head_dim": cfg.head_dim, "swa_head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim, "swa_v_head_dim": cfg.v_head_dim,
        "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe_d_ff,
        "num_experts_per_tok": cfg.moe_topk,
        "num_hidden_layers": cfg.n_layers,
        "n_routed_experts": cfg.n_experts,           # held here
        "vocab_size": cfg.vocab, "sliding_window": cfg.sliding_window,
        "sliding_window_size": cfg.sliding_window,
        "rope_theta": cfg.rope_theta, "swa_rope_theta": cfg.swa_rope_theta,
        "layernorm_epsilon": cfg.norm_eps,
        "attention_value_scale": cfg.attn_value_scale,
        "add_swa_attention_sink_bias": cfg.swa_sink,
        "tie_word_embeddings": cfg.tie_embeddings}
    assert {k: doc[k] for k in published} == published
    assert doc["add_full_attention_sink_bias"] is False
    assert doc["scoring_func"] == cfg.moe_router == "sigmoid"
    assert doc["topk_method"] == "noaux_tc" and cfg.moe_router_bias
    assert doc["n_group"] == doc["topk_group"] == 1 and not cfg.moe_n_group
    assert doc["norm_topk_prob"] and doc["routed_scaling_factor"] is None
    assert cfg.moe_routed_scale == 1.0 and doc["n_shared_experts"] is None
    assert cfg.moe_n_shared == 0 and cfg.norm == "rms"
    assert cfg.moe_n_routed == doc["published"]["n_routed_experts"] == 256
    assert doc["moe_layer_freq"][:cfg.n_layers] == [
        int(i >= cfg.n_dense_layers) for i in range(cfg.n_layers)]
    assert doc["hybrid_layer_pattern"][:cfg.n_layers] == [
        {"full": 0, "sliding": 1}[k] for k in cfg.layer_kinds]
    assert len(doc["hybrid_layer_pattern"]) == doc["published"][
        "num_hidden_layers"] == 48
    assert sorted(doc["reduced"]) == sorted(
        k for k in doc["published"] if k != "parameters") == [
            "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert doc["source"].endswith("XiaomiMiMo/MiMo-V2.5/blob/main/config.json")
    assert doc["vocab_size"] * 8 == doc["published"]["vocab_size"]
    # the cut as reckoned in the file: 3.430B parameters; the pools of
    # 2 full layers by n_blocks and 5 sliding layers' 64 rings of 2
    assert round(tfm.count_params(cfg) / 1e9, 3) == 3.430
    sv = doc["serve"]
    assert cfg.kv_pool_shapes(sv["n_blocks"], sv["block_size"]) == (
        (2, sv["n_blocks"], 128, 4 * 192), (2, sv["n_blocks"], 128, 4 * 128))
    assert cfg.sliding_pool_shapes(sv["max_active"], sv["block_size"]) == (
        (5, 64 * 2, 128, 8 * 192), (5, 64 * 2, 128, 8 * 128))
    # no request is preempted: every client at its longest fits
    assert sv["n_blocks"] >= 48 * 72 + 16 * 136
    assert "env" in sv and not sv["env"]
