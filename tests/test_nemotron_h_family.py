"""A model under a layer pattern (Nemotron 3 Super's block: Mamba-2
mixers with their state in slots of the cache manager, an attention
layer on 2 K/V heads without positions, top-k experts in a latent of
which a share is held) at a small size on the CPU, seeded weights,
against benchmarks/reference_nemotron_h.py."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference_nemotron_h as ref
from dmlc_tpu import telemetry
from dmlc_tpu.models import transformer as tfm
from dmlc_tpu.ops import dispatch
from dmlc_tpu.ops import mamba2
from dmlc_tpu.serving import InferenceEngine
from dmlc_tpu.serving.kv_cache import PagedKVCache
from held_experts import check_case

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 8  # block size of the small pools
PATTERN = "MEM*EME"


def small(**over):
    """d 64; 3 Mamba-2 layers of 8 heads x 8 channels in 2 groups with
    16 states, chunks of 16; 1 attention layer of 4 heads on 2 K/V
    heads of 16; 3 expert layers: 16 routed of which [4, 8) are held,
    top-6, a latent of 32, experts 48 wide, a shared expert of 96."""
    fields = dict(
        vocab=128, d_model=64, n_heads=4, head_dim=16, n_kv_heads=2,
        n_layers=len(PATTERN), n_experts=4, dtype="float32", moe_topk=6,
        attention="nemotron_h", layer_pattern=PATTERN,
        full_layers_rope=False, norm_eps=1e-5, moe_router="sigmoid",
        moe_n_routed=16, moe_held_start=4, moe_d_ff=48, moe_n_shared=1,
        moe_shared_d_ff=96, moe_routed_scale=5.0, moe_router_bias=True,
        moe_latent=32, moe_act="relu2", mamba_n_heads=8, mamba_head_dim=8,
        mamba_n_groups=2, mamba_state=16, mamba_chunk=16)
    fields.update(over)
    return tfm.TransformerConfig(**fields)


def spec_of(cfg):
    return ref.Spec(pattern=cfg.layer_pattern, top_k=cfg.moe_topk,
                    routed_scale=cfg.moe_routed_scale,
                    held_start=cfg.moe_held_start,
                    n_groups=cfg.mamba_n_groups)


_AS_SEEDED = {"ln", "ln_f", "norm", "a_log", "dt_bias", "d"}


def weights(cfg, seed=0):
    """Seeded weights five times init_params' scale (norms and the
    recurrence's own parameters as seeded), so that routing, the bias,
    the convolution and attention are far from uniform."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in _AS_SEEDED else a * 5, params)


def empty_cache(cfg, n_blocks=16, n_slots=4):
    return tuple(jnp.zeros(shape, cfg.jdtype)
                 for shape in cfg.kv_pool_shapes(n_blocks, BS)) + tuple(
        jnp.zeros(shape, dt) for shape, dt in cfg.state_slot_shapes(n_slots))


def ssm_inputs(seed, t, h, p, g, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, h)) - 1.0)
    rate = jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    b = jax.random.normal(ks[3], (t, g, n))
    c = jax.random.normal(ks[4], (t, g, n))
    return x, dt, rate, b, c


def test_the_tree_is_one_dict_a_layer_and_one_stack_of_experts():
    cfg = small()
    assert cfg.family == "nemotron_h" and cfg.served_only
    assert not cfg.latent and not cfg.hybrid
    assert cfg.layer_kinds == ("mamba", "moe", "mamba", "full", "moe",
                               "mamba", "moe")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "unembed", "ln_f", "layers", "experts"}
    m, e, _, a = params["layers"][:4]
    # z, xBC and dt side by side: 64 + (64 + 2 x 2 x 16) + 8 columns
    assert m["in_proj"].shape == (64, 64 + 128 + 8)
    assert m["conv"].shape == (4, 128) and m["conv_b"].shape == (128,)
    assert m["out_proj"].shape == (64, 64) and m["norm"].shape == (64,)
    assert {m[k].dtype for k in ("a_log", "dt_bias", "d")} == {
        jnp.dtype("float32")}
    # softplus(dt_bias) log-uniform in [0.001, 0.1], exp(a_log) in [1, 16]
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert (dt >= 0.001 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert (np.exp(m["a_log"]) >= 1).all() and (np.exp(m["a_log"]) <= 16).all()
    assert a["wq"].shape == (64, 4, 16) and a["wk"].shape == (64, 2, 16)
    assert e["gate"].shape == (64, 16) and e["gate_bias"].shape == (16,)
    assert e["w_down"].shape == (64, 32) and e["w_up"].shape == (32, 64)
    assert e["s_in"].shape == (64, 96) and "s_gate" not in e
    # the held experts of the three expert layers, in the latent, no gate
    assert set(params["experts"]) == {"w_in", "w_out"}
    assert params["experts"]["w_in"].shape == (3 * 4, 32, 48)
    assert params["experts"]["w_out"].shape == (3 * 4, 48, 32)
    assert tfm.count_params(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    # the pools hold the attention layer alone, the slots the mixers
    assert cfg.kv_pool_shapes(16, BS) == ((1, 16, BS, 2, 16),) * 2
    assert cfg.state_slot_shapes(4) == (
        ((3, 4, 8, 8, 16), "float32"), ((3, 4, 3, 128), "float32"))
    assert tfm.decode_flops_per_token(cfg, 64) > 0
    with pytest.raises(NotImplementedError):
        tfm.unsharded_loss(params, jnp.zeros((1, 8), jnp.int32),
                           jnp.zeros((1, 8), jnp.int32), cfg)


@pytest.mark.parametrize("t,real", [(37, 37), (128, 128), (150, 150),
                                    (192, 131)],
                         ids=["short", "whole_chunks", "ragged",
                              "padded_tail"])
def test_chunked_scan_against_the_token_recurrence(t, real):
    """T a multiple of the chunk and not, and a padded tail (dt = 0
    past the last real token) that must leave the state where the last
    real token put it."""
    x, dt, rate, b, c = ssm_inputs(1, t, 8, 8, 2, 16)
    dt = jnp.where((jnp.arange(t) >= real)[:, None], 0.0, dt)
    y, s_t = mamba2.ssd_chunk_scan(x, dt, rate, b, c, chunk=64)
    want = ref.recurrence(x[:real], dt[:real], rate, b[:real], c[:real])
    # float32 sums of some hundred terms in another order: outputs of
    # a few units, up to 50
    np.testing.assert_allclose(np.asarray(y[:real]), np.asarray(want),
                               atol=5e-5, rtol=5e-5)
    _, s_real = mamba2.ssd_chunk_scan(x[:real], dt[:real], rate, b[:real],
                                      c[:real], chunk=64)
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(s_real),
                               atol=1e-5)
    assert s_t.dtype == jnp.float32 and s_t.shape == (8, 8, 16)


@pytest.mark.parametrize("n_live", [3, 0], ids=["some_live", "none_live"])
def test_state_step_kernel_against_its_lax_form(n_live):
    """Interpreted, at the kernel's own tile sizes (2 groups of 16
    heads of 64 x 128): live rows updated, a dead row's slot and the
    free ones bit for bit as they were, live rows in any order, the
    state float32."""
    rows, h, p, g, n = 5, 32, 64, 2, 128
    x, dt, rate, b, c = ssm_inputs(2, rows, h, p, g, n)
    dtx, decay = x * dt[..., None], jnp.exp(-dt * rate)
    state = jax.random.normal(jax.random.PRNGKey(3), (9, h, p, n))
    slots = jnp.array([3, 7, 1, 0, 5], jnp.int32)
    live = jnp.array([True, False, True, True, False]) if n_live \
        else jnp.zeros(rows, bool)
    y_lax, s_lax = mamba2.ssm_state_step(dtx, decay, b, c, state, slots, live,
                                         impl="lax")
    y_pl, s_pl = mamba2.ssm_state_step(dtx, decay, b, c, state, slots, live,
                                       impl="pallas")
    alive = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y_pl)[alive],
                               np.asarray(y_lax)[alive], atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_lax),
                               atol=2e-6)
    assert s_pl.dtype == jnp.float32
    untouched = sorted(set(range(9)) - set(np.asarray(slots)[alive].tolist()))
    for got in (s_pl, s_lax):
        np.testing.assert_array_equal(np.asarray(got)[untouched],
                                      np.asarray(state)[untouched])
    if n_live:
        # one step from a zero state is the recurrence's first step
        want = ref.recurrence(x[:1], dt[:1], rate, b[:1], c[:1])
        y0, _ = mamba2.ssm_state_step(dtx, decay, b, c,
                                      jnp.zeros_like(state), slots, live,
                                      impl="pallas")
        np.testing.assert_allclose(np.asarray(y0[0]), np.asarray(want[0]),
                                   atol=2e-5)


@pytest.fixture(scope="module")
def model():
    cfg = small()
    params = weights(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32)
    want = [np.asarray(ref.logits_at(params, row, np.arange(64),
                                     spec=spec_of(cfg))) for row in ids]
    return cfg, params, ids, want


@pytest.mark.parametrize("n", [32, 29, 17], ids=[
    "whole_chunks", "padded_tail", "one_chunk_and_a_token"])
def test_prefill_logits_against_the_reference(model, n):
    """Prompt lengths that are and are not multiples of the chunk (16),
    padded to whole blocks: the logits of the last real token, and only
    the sequence's own pages and its own slot written."""
    cfg, params, ids, want = model
    padded = -(-n // BS) * BS
    blocks = np.array([3, 5, 7, 9], np.int32)[:padded // BS]
    prompt = np.zeros((1, padded), np.int32)
    prompt[0, :n] = ids[0, :n]
    logits, (k_pool, v_pool, state, tails), (moe,) = tfm.prefill_paged(
        params, prompt, np.array([n - 1], np.int32), empty_cache(cfg),
        blocks, (np.array([2], np.int32),), cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), want[0][n - 1],
                               atol=1e-4)
    for pool in (k_pool, v_pool):
        written = np.flatnonzero(np.abs(np.asarray(pool)).sum(
            axis=(0, 2, 3, 4)))
        assert written.tolist() == blocks.tolist()
    for a in (state, tails):
        used = np.flatnonzero(np.abs(np.asarray(a)).reshape(
            a.shape[0], a.shape[1], -1).sum(axis=(0, 2)))
        assert used.tolist() == [2]
    assert state.dtype == jnp.float32
    moe = np.asarray(moe)
    assert moe.shape == (3, cfg.n_experts + 2)
    assert (moe[:, -2] == n * cfg.moe_topk).all()  # pad tokens left out


def test_a_long_prompt_walked_in_passes_equals_one_pass(model, monkeypatch):
    """A prompt longer than MAMBA_PREFILL_ROWS goes through a Mamba-2
    layer some rows a pass, the state and the convolution's last inputs
    carried between passes: the same logits, state and tail as one
    pass over all of it."""
    cfg, params, ids, want = model
    n = 61
    prompt = np.zeros((1, 64), np.int32)
    prompt[0, :n] = ids[0, :n]
    args = (params, prompt, np.array([n - 1], np.int32), empty_cache(cfg),
            np.arange(8, dtype=np.int32), (np.array([3], np.int32),), cfg)
    whole = tfm.prefill_paged(*args)
    monkeypatch.setattr(tfm, "MAMBA_PREFILL_ROWS", 16)
    passes = tfm.prefill_paged(*args)
    np.testing.assert_allclose(np.asarray(passes[0][0]), want[0][n - 1],
                               atol=1e-4)
    for got, one in zip((passes[0], *passes[1]), (whole[0], *whole[1])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(one),
                                   atol=2e-5)


def test_the_state_and_tail_prefill_hands_over_are_the_recurrences(model):
    """The slot after a prefill of n tokens holds what the token
    recurrence holds after n tokens, and the convolution's last three
    inputs as projected: a decode step from it equals a prefill of n +
    1 tokens."""
    cfg, params, ids, _ = model
    n = 21
    prompt = np.zeros((1, 24), np.int32)
    prompt[0, :n] = ids[0, :n]
    blocks = np.array([1, 2, 3], np.int32)
    _, cache, _ = tfm.prefill_paged(
        params, prompt, np.array([n - 1], np.int32), empty_cache(cfg),
        blocks, (np.array([1], np.int32),), cfg)
    longer = prompt.copy()
    longer[0, n] = ids[0, n]
    _, (_, _, state_n1, tails_n1), _ = tfm.prefill_paged(
        params, longer, np.array([n], np.int32), empty_cache(cfg), blocks,
        (np.array([1], np.int32),), cfg)
    tables = np.zeros((1, 4), np.int32)
    tables[0, :3] = blocks
    _, (_, _, state, tails), _ = tfm.decode_paged(
        params, ids[:1, n:n + 1], np.array([[n]], np.int32), cache, tables,
        np.array([n], np.int32), (np.array([1], np.int32),), cfg)
    np.testing.assert_allclose(np.asarray(state[:, 1]),
                               np.asarray(state_n1[:, 1]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(tails[:, 1]),
                               np.asarray(tails_n1[:, 1]), atol=1e-5)


@pytest.mark.parametrize("others", [False, True], ids=["alone", "in_a_batch"])
def test_prefill_then_decode_through_cache_and_slots(model, others):
    """Prefill of 21 tokens into slot 2 (padded to 24: the padding must
    not touch the state), then 43 teacher-forced decode steps against
    the reference's plain full forward; beside a dead row, or beside a
    second sequence in slot 0 whose logits are held to its own
    reference too (a slot leaks nothing)."""
    cfg, params, ids, want = model
    n = 21
    blocks = np.arange(1, 9, dtype=np.int32)
    padded = np.zeros((1, 24), np.int32)
    padded[0, :n] = ids[0, :n]
    logits, cache, _ = tfm.prefill_paged(
        params, padded, np.array([n - 1], np.int32),
        empty_cache(cfg, n_blocks=24), blocks[:3], (np.array([2], np.int32),),
        cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), want[0][n - 1],
                               atol=1e-4)
    tables = np.zeros((3, 8), np.int32)
    tables[0] = blocks
    slots = np.array([2, 0, 0], np.int32)
    if others:
        second = np.zeros((1, 24), np.int32)
        second[0, :n] = ids[1, :n]
        logits, cache, _ = tfm.prefill_paged(
            params, second, np.array([n - 1], np.int32), cache,
            np.arange(9, 12, dtype=np.int32), (np.array([0], np.int32),), cfg)
        np.testing.assert_allclose(np.asarray(logits[0]), want[1][n - 1],
                                   atol=1e-4)
        tables[1] = np.arange(9, 17)
    step = jax.jit(tfm.decode_paged, static_argnums=(7,))
    for t in range(n, 64):
        live = np.array([t, t if others else 0, 0], np.int32)
        logits, cache, (moe,) = step(
            params, np.array([[ids[0, t]], [ids[1, t]], [0]], np.int32),
            live[:, None], cache, tables, live, (slots,), cfg)
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[0][t],
                                   atol=2e-4)
        if others:
            np.testing.assert_allclose(np.asarray(logits[1, 0]), want[1][t],
                                       atol=2e-4)
        assert (np.asarray(moe)[:, -2] == (1 + others) * cfg.moe_topk).all()
    # dead rows carry slot 0: without a second sequence it stays zero
    if not others:
        assert not np.asarray(cache[2][:, 0]).any()
        assert not np.asarray(cache[3][:, 0]).any()


def _one_expert_layer(cfg, seed=4):
    params = weights(cfg, seed)
    p = {**params["layers"][1],
         **jax.tree.map(lambda a: a[:cfg.n_experts], params["experts"])}
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 24, cfg.d_model))
    return p, x


def _reference_layer(x, p, cfg):
    return ref._experts(x[0], p, p, 0, cfg.n_experts, lambda a: a,
                        spec_of(cfg))


def test_the_bias_steers_an_ungrouped_top_k():
    cfg = small()
    p, x = _one_expert_layer(cfg)
    scores = jax.nn.sigmoid(x[0] @ p["gate"])
    want_w, want_i = ref.route(scores, p["gate_bias"], spec_of(cfg))
    plain_i = jax.lax.top_k(scores, cfg.moe_topk)[1]
    # the bias changed some token's picks, the weights are the unbiased
    # scores' over their sum times 5
    assert (np.sort(np.asarray(plain_i)) != np.sort(np.asarray(want_i))).any()
    s = np.take_along_axis(np.asarray(scores), np.asarray(want_i), -1)
    np.testing.assert_allclose(np.asarray(want_w),
                               5.0 * s / s.sum(-1, keepdims=True), rtol=1e-6)
    # and the program's counts are of those picks
    _, counts = tfm._moe_held_ffn(x, p, cfg)
    held = [(np.asarray(want_i) == cfg.moe_held_start + j).sum()
            for j in range(cfg.n_experts)]
    assert np.asarray(counts)[:-1].tolist() == held + [24 * cfg.moe_topk]


@pytest.mark.parametrize("case", ["rule", "overflow", "last_group",
                                  "sparse"])
def test_the_held_experts_under_the_tile_rule(case, monkeypatch):
    """Experts in a latent, relu-squared, under a biased top-k, through
    the trip loop: the rule's one trip and forced 8-row trips, routing
    skewed onto the held experts past the rule's tile (256 tokens put
    1,024 pairs on a tile of 512), the layer's groups last in a junk
    stack, and a layer whose pairs are sparse over 64 held experts (96
    tokens expect 288 pairs: one trip of 576 rows holds the 576 that the
    skew puts here, where 512-row trips took two): the reference's
    expert layer every time."""
    cfg = small(**({"n_experts": 64, "moe_n_routed": 128,
                    "moe_held_start": 0} if case == "sparse" else {}))
    p, x = _one_expert_layer(cfg, seed=7)
    if case in ("overflow", "sparse"):
        n = 256 if case == "overflow" else 96
        x = jax.random.normal(jax.random.PRNGKey(8), (1, n, cfg.d_model))
    check_case(case, p, x, cfg, _reference_layer, monkeypatch, atol=1e-4)


def test_the_four_shares_of_a_latent_layer_add_up():
    """The partial results of all four shares of one layer (experts
    [0,4), [4,8), [8,12), [12,16) of 16), each up-projected from its own
    partial latent sum, the shared expert counted once, equal the uncut
    reference: a share that holds all 16."""
    whole = small(n_experts=16, moe_held_start=0)
    p, x = _one_expert_layer(whole, seed=6)
    want = _reference_layer(x, p, whole)
    hidden = jnp.square(jax.nn.relu(x @ p["s_in"]))
    shared = hidden @ p["s_out"]
    total = shared
    pairs = 0
    for start in range(0, 16, 4):
        cfg = small(moe_held_start=start)
        mine = dict(p, **{name: p[name][start:start + 4]
                          for name in ("w_in", "w_out")})
        y, counts = tfm._moe_held_ffn(x, mine, cfg)
        np.testing.assert_allclose(
            np.asarray(y[0]), np.asarray(_reference_layer(x, mine, cfg)),
            atol=5e-5)
        total = total + (y - shared)
        pairs += int(np.asarray(counts)[:-2].sum())
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=1e-4)
    assert pairs == 24 * whole.moe_topk


def test_slots_bound_admission_beside_blocks():
    cfg = small()
    cache = PagedKVCache(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, n_blocks=16, block_size=BS,
        pool_shapes=cfg.kv_pool_shapes(16, BS),
        state_shapes=cfg.state_slot_shapes(2))
    assert cache.n_slots == 2 and cache.can_reserve(8)
    assert cache.allocate(1, 8) and cache.allocate(2, 8)
    # 14 blocks are free, no slot is: nothing more is admitted
    assert cache.n_free_blocks == 14 and not cache.can_reserve(8)
    assert not cache.allocate(3, 8)
    cache.free(1)
    assert cache.allocate(3, 8) and cache.slot_ids([3]).tolist() == [0]
    assert [p.shape for p in cache.device_pools()] == [
        (1, 16, BS, 2, 16), (1, 16, BS, 2, 16), (3, 2, 8, 8, 16),
        (3, 2, 3, 128)]


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


def test_the_engine_end_to_end_takes_and_frees_slots():
    """Through InferenceEngine: slots taken and freed, admission bounded
    by the slots (one slot serves three requests one after another and
    returns what three slots return), the same ids twice, a preempted
    request resumed to the same ids, and the counters of the state."""
    cfg = small()
    prompts = [[(5 * i + j) % cfg.vocab for i in range(14)] for j in (1, 2, 3)]
    before = telemetry.counters_snapshot().get("serving", {})
    apart, none = _generate_all(cfg, prompts, 12, n_blocks=24, max_active=3)
    again, _ = _generate_all(cfg, prompts, 12, n_blocks=24, max_active=3)
    queued, _ = _generate_all(cfg, prompts, 12, n_blocks=24, max_active=1)
    assert apart == again == queued and none == 0
    assert all(len(g) == 12 for g in apart)
    tight, some = _generate_all(cfg, prompts[:2], 12, n_blocks=5,
                                max_active=2)
    assert some > 0 and tight == apart[:2]
    c = telemetry.counters_snapshot()["serving"]
    grew = {k: c[k] - before.get(k, 0) for k in (
        "state_slot_allocs", "state_slot_steps", "ssm_state_rw_bytes",
        "paged_decode_steps", "moe_pairs_total")}
    assert grew["state_slot_allocs"] == 3 * 3 + 2 + some
    # 2 x 3 layers x [8, 8, 16] float32 a live row and step
    assert grew["ssm_state_rw_bytes"] \
        == 2 * 3 * 8 * 8 * 16 * 4 * grew["state_slot_steps"]
    assert 0 < grew["state_slot_steps"] <= 3 * grew["paged_decode_steps"]
    assert grew["moe_pairs_total"] > 0
    assert c.get("kda_state_rw_bytes", 0) == before.get(
        "kda_state_rw_bytes", 0)


def test_the_engine_counts_the_grouped_products():
    """One request alone through InferenceEngine: a prefill and one
    decode step.  At this size the rule's tile holds every pair a
    program can route here, so each of the 3 expert layers makes one
    trip of 2 grouped products (relu-squared: no gate matrix) a program
    call, pad tokens and dead rows included."""
    cfg = small()
    assert all(tfm.moe_tile(n, cfg) == n * cfg.n_experts for n in (2, 16))
    before = telemetry.counters_snapshot().get("serving", {})
    eng = InferenceEngine(weights(cfg), cfg, n_blocks=8, block_size=BS,
                          max_active=2, queue_depth=4)
    eng.start()
    try:
        req = eng.submit([(3 * i + 1) % cfg.vocab for i in range(14)], 2)
        assert req.wait(120) and req.error is None, req.error
    finally:
        eng.close()
    c = telemetry.counters_snapshot()["serving"]
    grew = {k: c[k] - before.get(k, 0) for k in (
        "prefill_count", "paged_decode_steps", "moe_grouped_calls")}
    assert grew["prefill_count"] == 1 and grew["paged_decode_steps"] == 1
    assert grew["moe_grouped_calls"] == 2 * 3 * 2


def test_speculation_is_refused_for_recurrent_layers(monkeypatch):
    monkeypatch.setenv("DMLC_SERVE_SPEC_K", "2")
    cfg = small()
    with pytest.raises(ValueError, match="recurrent"):
        InferenceEngine(weights(cfg), cfg, n_blocks=8, block_size=BS,
                        max_active=2)


@pytest.mark.parametrize("control,moves", [
    ({"quantize": jnp.float8_e4m3fn}, 1e-3),
    ({"state_dtype": jnp.bfloat16}, 5e-5)],
    ids=["float8_operands", "bf16_state"])
def test_the_reference_controls_move_the_logits(model, control, moves):
    """The rounded state moves the logits far less than rounded
    operands do (a head forgets within tens of tokens at these seeds),
    and still fifty times what float32 rounding does."""
    cfg, params, ids, want = model
    low = np.asarray(ref.logits_at(params, ids[0], np.arange(64),
                                   spec=spec_of(cfg), **control))
    assert np.abs(low - want[0]).max() > moves


def test_reference_constants_equal_the_configuration_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3-super-ep4-serve.json")) as f:
        doc = json.load(f)
    cfg = tfm.TransformerConfig(**doc["model"])
    assert ref.NEMOTRON3 == spec_of(cfg) == ref.Spec(
        pattern=doc["hybrid_override_pattern"],
        top_k=doc["num_experts_per_tok"],
        routed_scale=doc["routed_scaling_factor"],
        held_start=doc["model"]["moe_held_start"], n_groups=doc["n_groups"])
    assert ref.RMS_EPS == doc["layer_norm_epsilon"] == doc["norm_eps"] \
        == cfg.norm_eps
    published = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "mamba_num_heads": cfg.mamba_n_heads,
        "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.mamba_n_groups,
        "ssm_state_size": cfg.mamba_state, "conv_kernel": cfg.mamba_conv_size,
        "chunk_size": cfg.mamba_chunk,
        "moe_intermediate_size": cfg.moe_d_ff,
        "moe_latent_size": cfg.moe_latent,
        "moe_shared_expert_intermediate_size": cfg.moe_shared_d_ff,
        "n_shared_experts": cfg.moe_n_shared,
        "num_experts_per_tok": cfg.moe_topk,
        "num_hidden_layers": cfg.n_layers,
        "hybrid_override_pattern": cfg.layer_pattern,
        "n_routed_experts": cfg.n_experts,            # held here
        "vocab_size": cfg.vocab,
        "routed_scaling_factor": cfg.moe_routed_scale}
    assert {k: doc[k] for k in published} == published
    assert doc["expand"] * cfg.d_model == cfg.mamba_d_inner == 8192
    assert doc["mlp_hidden_act"] == "relu2" == cfg.moe_act
    assert doc["n_group"] == 1 and cfg.moe_n_group == 0  # no group limit
    assert doc["use_conv_bias"] and not doc["mamba_proj_bias"] \
        and not doc["attention_bias"] and not doc["mlp_bias"]
    assert not doc["tie_word_embeddings"] and not cfg.tie_embeddings
    assert not cfg.full_layers_rope and cfg.moe_router_bias
    assert cfg.moe_n_routed == doc["published"]["n_routed_experts"] == 512
    assert sorted(doc["reduced"]) == sorted(doc["published"]["reduced"]) \
        == sorted(doc["reduced_why"])
    # the kept layers are the published pattern's first eleven
    whole = doc["published"]["hybrid_override_pattern"]
    assert len(whole) == doc["published"]["num_hidden_layers"] == 88
    assert whole.startswith(cfg.layer_pattern)
    assert (whole.count("M"), whole.count("E"), whole.count("*")) \
        == (40, 40, 8)
    assert cfg.layer_kinds.count("mamba") == 5 == cfg.layer_kinds.count("moe")
    # the cut as ISSUE 38 reckons it: 4.648B parameters; 4.19 MB of state
    # a row and Mamba-2 layer and 61,440 B of tail; 0.30 GB of K/V pool
    assert round(tfm.count_params(cfg) / 1e9, 3) == 4.648
    sv = doc["serve"]
    k_pool, v_pool = cfg.kv_pool_shapes(sv["n_blocks"], sv["block_size"])
    (state, _), (tails, tail_dt) = cfg.state_slot_shapes(sv["max_active"])
    assert k_pool == v_pool == (1, sv["n_blocks"], 128, 2, 128)
    assert state == (5, sv["max_active"], 128, 64, 128)
    assert tails == (5, sv["max_active"], 240, 128) and tail_dt == "bfloat16"
    assert np.prod(state[2:]) * 4 == 4194304
    assert np.prod(tails[2:]) * 2 == 61440


def test_the_kernels_lower_for_the_tpu_at_published_widths():
    """What the chip will be asked: the state step at 128 rows of 128
    heads of 64 x 128 in 8 groups over all 5 layers' slots as one
    run."""
    assert mamba2.state_step_supports(128, 8, 64, 128)
    assert not mamba2.state_step_supports(8, 2, 8, 16)
    f32 = jnp.float32
    group = jax.ShapeDtypeStruct((128, 8, 128), f32)
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        text = jax.jit(mamba2.ssm_state_step).trace(
            jax.ShapeDtypeStruct((128, 128, 64), f32),
            jax.ShapeDtypeStruct((128, 128), f32), group, group,
            jax.ShapeDtypeStruct((5 * 128, 128, 64, 128), f32),
            jax.ShapeDtypeStruct((128,), jnp.int32),
            jax.ShapeDtypeStruct((128,), jnp.bool_)).lower(
                lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "ssm_state_step"') == 1
    assert "tpu_custom_call" in text
