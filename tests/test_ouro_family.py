"""The MHA family looped (Ouro's LoopLM block: the same layers run
several times a token, each pass with its own cache layers, sandwich
norms, the final norm closing every pass) at a small size on the CPU,
seeded weights, against benchmarks/reference_ouro.py."""

import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks import reference_ouro as ref
from dmlc_tpu import telemetry
from dmlc_tpu.models import transformer as tfm
from dmlc_tpu.ops import dispatch
from dmlc_tpu.serving import InferenceEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4    # block size of the small pools
T = 40    # the reference's sequence
_NORMS = ("ln1", "ln1_post", "ln2", "ln2_post", "ln_f")


def small(**over):
    """d 64, 4 heads of 16 (as many K/V heads), SwiGLU 96, 3 layers run
    3 times, float32."""
    fields = dict(vocab=128, d_model=64, n_heads=4, head_dim=16, d_ff=96,
                  n_layers=3, n_experts=1, microbatches=1, dtype="float32",
                  rope_theta=1e6, norm_eps=1e-6, loop_passes=3,
                  sandwich_norm=True)
    fields.update(over)
    return tfm.TransformerConfig(**fields)


def spec_of(cfg):
    return ref.Spec(passes=cfg.loop_passes, rope_theta=cfg.rope_theta,
                    norm_eps=cfg.norm_eps)


def weights(cfg, seed=0):
    """Seeded weights three times init_params' scale, so that attention
    is far from uniform, and every norm scale drawn away from 1 on its
    own, so that a norm read in another's place shows."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def seeded(path, a):
        if path[-1].key in _NORMS:
            return (1.0 + 0.5 * jax.random.normal(next(keys), a.shape)
                    ).astype(a.dtype)
        return a * 3

    return jax.tree_util.tree_map_with_path(seeded, params)


def empty_pools(cfg, n_blocks=24):
    return [jnp.zeros(s, cfg.jdtype) for s in cfg.kv_pool_shapes(n_blocks, BS)]


@pytest.fixture(scope="module")
def model():
    cfg = small()
    params = weights(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab, (2, T)).astype(np.int32)
    want = [np.asarray(ref.logits_at(params, row, np.arange(T),
                                     spec=spec_of(cfg))) for row in ids]
    return cfg, params, ids, want


def _prefill(cfg, params, pools, ids, n, blocks):
    padded = np.zeros((1, len(blocks) * BS), np.int32)
    padded[0, :n] = ids[:n]
    logits, *pools = tfm.forward_prefill_paged(
        params, padded, np.array([n - 1], np.int32), *pools, blocks, cfg)
    return np.asarray(logits[0]), pools


def _decode_steps(cfg, params, pools, ids, tables, start, stop, rows=1):
    """Teacher-forced decode of ``ids[r]`` from ``start`` to ``stop`` in
    rows ``r < rows`` of a batch of three; the logits of each step."""
    step = jax.jit(lambda p, i, pos, k, v, t, n: tfm.forward_decode_paged(
        p, i, pos, k, v, t, n, cfg))
    out = []
    for t in range(start, stop):
        live = np.array([t if r < rows else 0 for r in range(3)], np.int32)
        feed = np.array([[ids[r][t] if r < rows else 0] for r in range(3)],
                        np.int32)
        logits, *pools = step(params, feed, live[:, None], *pools, tables,
                              live)
        out.append(np.asarray(logits[:, 0]))
    return out, pools


def test_the_tree_the_pools_and_the_count():
    cfg = small()
    assert cfg.family == "mha" and cfg.served_only and cfg.looped
    assert not tfm.flagship_config().looped
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "unembed", "ln_f", "layers", "w_exit",
                           "b_exit"}
    assert tfm.per_layer_params(params) is params
    layer = params["layers"][0]
    assert len(params["layers"]) == 3
    assert set(layer) == {"ln1", "ln1_post", "ln2", "ln2_post", "wq", "wk",
                          "wv", "wo", "w_in", "w_gate", "w_out"}
    # q, k and v head-major (transformer._heads), o as the family has it
    assert layer["wq"].shape == (4, 64, 16) and layer["wo"].shape == (4, 16, 64)
    assert layer["w_in"].shape == (64, 96) and layer["w_out"].shape == (96, 64)
    assert params["w_exit"].shape == (64,) and params["b_exit"].shape == (1,)
    # the published count: 4 E^2-sized projections, 3 of the SwiGLU and
    # 4 norm scales a layer, embedding, head, final norm and exit gate
    per_layer = 4 * 64 * 64 + 3 * 64 * 96 + 4 * 64
    assert tfm.count_params(cfg) == 3 * per_layer + 2 * 128 * 64 + 64 + 65
    # a cache layer per layer and pass, pass t's layer l at t x 3 + l
    assert cfg.kv_pool_shapes(16, BS) == ((9, 16, BS, 4, 16),) * 2
    assert small(loop_passes=1).kv_pool_shapes(16, BS)[0][0] == 3
    # each pass costs the layers' operations again, the head once
    one = tfm.decode_flops_per_token(small(loop_passes=1), 32)
    head = 2 * 64 * 128
    assert tfm.decode_flops_per_token(cfg, 32) - head == pytest.approx(
        3 * (one - head))
    with pytest.raises(NotImplementedError):
        tfm.unsharded_loss(params, jnp.zeros((1, 8), jnp.int32),
                           jnp.zeros((1, 8), jnp.int32), cfg)


@pytest.mark.parametrize("n", [16, 13, 5], ids=["whole_blocks",
                                                "padded_tail", "one_block"])
def test_prefill_logits_and_pages_against_the_reference(model, n):
    """The logits of the last real token; every pass's K/V in its own
    cache layers at the sequence's blocks and nowhere else."""
    cfg, params, ids, want = model
    blocks = np.array([3, 5, 7, 9], np.int32)[:-(-n // BS)]
    logits, (k_pool, v_pool) = _prefill(cfg, params, empty_pools(cfg, 16),
                                        ids[0], n, blocks)
    # float32 sums of some tens of terms in another order, through nine
    # layer applications: logits of about one, up to 1e-5 apart
    np.testing.assert_allclose(logits, want[0][n - 1], atol=1e-4)
    for pool in (k_pool, v_pool):
        pool = np.asarray(pool)
        for layer in range(9):
            written = np.flatnonzero(np.abs(pool[layer]).sum(axis=(1, 2, 3)))
            assert written.tolist() == blocks.tolist()
        # a later pass attends other keys than the first: its own
        for later in (3, 6):
            assert np.abs(pool[later] - pool[0])[blocks].max() > 0.1


@pytest.mark.parametrize("others", [False, True], ids=["alone", "in_a_batch"])
def test_prefill_then_decode_through_the_cache(model, others):
    """Prefill of 13 tokens (padded to 16), then teacher-forced decode
    steps to 40 against the reference's plain full forward; beside a
    dead row, or beside a second sequence whose logits are held to its
    own reference too."""
    cfg, params, ids, want = model
    n = 13
    pools = empty_pools(cfg)
    logits, pools = _prefill(cfg, params, pools, ids[0], n,
                             np.arange(1, 5, dtype=np.int32))
    np.testing.assert_allclose(logits, want[0][n - 1], atol=1e-4)
    tables = np.zeros((3, 10), np.int32)
    tables[0] = np.arange(1, 11)
    if others:
        logits, pools = _prefill(cfg, params, pools, ids[1], n,
                                 np.arange(11, 15, dtype=np.int32))
        np.testing.assert_allclose(logits, want[1][n - 1], atol=1e-4)
        tables[1] = np.arange(11, 21)
    steps, _ = _decode_steps(cfg, params, pools, ids, tables, n, T,
                             rows=1 + others)
    for t, logits in zip(range(n, T), steps):
        np.testing.assert_allclose(logits[0], want[0][t], atol=2e-4)
        if others:
            np.testing.assert_allclose(logits[1], want[1][t], atol=2e-4)


def _first_pass_pages_in_every_pass(x, carry, one_pass, params, cfg):
    """A wrong loop: every pass reads and writes the first pass's cache
    layers."""
    def body(t, state):
        x, carry = state
        x, carry = one_pass(0, x, carry)
        return tfm._norm(x, params["ln_f"], cfg), carry
    return lax.fori_loop(0, cfg.loop_passes, body, (x, carry))


def _no_norm_between_passes(x, carry, one_pass, params, cfg):
    """A wrong loop: the final norm only after the last pass."""
    def body(t, state):
        x, carry = state
        return one_pass(t * cfg.n_layers, x, carry)
    x, carry = lax.fori_loop(0, cfg.loop_passes, body, (x, carry))
    return tfm._norm(x, params["ln_f"], cfg), carry


def _worst_decode_gap(model, monkeypatch=None, wrong=None, swap=False):
    """The largest distance from the reference's logits over a prefill
    of 13 tokens and the decode steps to 40, with the loop replaced by
    ``wrong`` for decode (and prefill where ``wrong`` is not the cache
    variant), or with ``ln1_post`` and ``ln2_post`` swapped."""
    cfg, params, ids, want = model
    if swap:
        params = dict(params, layers=[
            dict(p, ln1_post=p["ln2_post"], ln2_post=p["ln1_post"])
            for p in params["layers"]])
    if wrong is _no_norm_between_passes:
        monkeypatch.setattr(tfm, "_loop_passes", wrong)
    n = 13
    logits, pools = _prefill(cfg, params, empty_pools(cfg), ids[0], n,
                             np.arange(1, 5, dtype=np.int32))
    worst = np.abs(logits - want[0][n - 1]).max()
    if wrong is _first_pass_pages_in_every_pass:
        monkeypatch.setattr(tfm, "_loop_passes", wrong)
    tables = np.zeros((3, 10), np.int32)
    tables[0] = np.arange(1, 11)
    steps, _ = _decode_steps(cfg, params, pools, ids, tables, n, T)
    return max([worst] + [np.abs(s[0] - want[0][t]).max()
                          for t, s in zip(range(n, T), steps)])


@pytest.mark.parametrize("wrong", [
    None, _first_pass_pages_in_every_pass, _no_norm_between_passes, "swap"],
    ids=["as_built", "cache_shared_across_passes",
         "no_final_norm_between_passes", "post_norms_swapped"])
def test_a_misplaced_cache_or_norm_fails_the_reference(model, monkeypatch,
                                                       wrong):
    """Each mistake moves the logits by far more than the tolerance the
    program as built keeps to."""
    if wrong == "swap":
        gap = _worst_decode_gap(model, swap=True)
    else:
        gap = _worst_decode_gap(model, monkeypatch, wrong)
    if wrong is None:
        assert gap < 2e-4, gap
    else:
        assert gap > 100 * 2e-4, gap


def test_one_pass_with_sandwich_norms_is_the_reference_at_one_pass():
    """``loop_passes`` 1 under sandwich norms takes the one-pass prefill
    trunk and the plain decode: the reference at one pass."""
    cfg = small(loop_passes=1)
    params = weights(cfg, seed=3)
    ids = np.random.default_rng(3).integers(0, cfg.vocab, T).astype(np.int32)
    want = np.asarray(ref.logits_at(params, ids, np.arange(T),
                                    spec=spec_of(cfg)))
    n = 13
    logits, pools = _prefill(cfg, params, empty_pools(cfg), ids, n,
                             np.arange(1, 5, dtype=np.int32))
    np.testing.assert_allclose(logits, want[n - 1], atol=1e-4)
    tables = np.zeros((3, 10), np.int32)
    tables[0] = np.arange(1, 11)
    steps, _ = _decode_steps(cfg, params, pools, [ids], tables, n, 20)
    for t, logits in zip(range(n, 20), steps):
        np.testing.assert_allclose(logits[0], want[t], atol=2e-4)


#: sha256 of the jaxprs of the flagship's serving programs at a small
#: size (bf16, head_dim 128, 2 layers, the per-layer tree), recorded
#: from the program before the looped family existed: one pass without
#: sandwich norms traces exactly what it traced.  A deliberate change to
#: the flagship's programs records them again
_FLAGSHIP_JAXPRS = {
    "decode": "a374cd74814588fc563afa64a0820c17d56d1468922f63a13dfe005805ca5b1e",
    "prefill": "9c52d5e2a0577932c25723608e7e2f90dea9202e572fc2de93253d50a89e486a",
}


def test_one_pass_traces_the_flagship_programs_as_before():
    cfg = tfm.TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                head_dim=128, d_ff=96, n_layers=2,
                                n_experts=1, microbatches=1,
                                dtype="bfloat16")
    assert cfg.loop_passes == 1 and not cfg.sandwich_norm
    params = tfm.per_layer_params(tfm.init_params(jax.random.PRNGKey(0), cfg))
    pools = [jnp.zeros(s, cfg.jdtype) for s in cfg.kv_pool_shapes(8, 4)]
    i32 = np.int32
    with dispatch.force_kernel_mode(dispatch.LAX):
        decode = jax.make_jaxpr(lambda p, i, pos, k, v, t, n:
                                tfm.forward_decode_paged(p, i, pos, k, v, t,
                                                         n, cfg))(
            params, np.zeros((2, 1), i32), np.zeros((2, 1), i32), *pools,
            np.zeros((2, 4), i32), np.zeros((2,), i32))
        prefill = jax.make_jaxpr(lambda p, i, last, k, v, b:
                                 tfm.forward_prefill_paged(p, i, last, k, v,
                                                           b, cfg))(
            params, np.zeros((1, 8), i32), np.zeros((1,), i32), *pools,
            np.zeros((2,), i32))
    got = {name: hashlib.sha256(str(j).encode()).hexdigest()
           for name, j in (("decode", decode), ("prefill", prefill))}
    assert got == _FLAGSHIP_JAXPRS
    assert "while" not in str(decode)


def test_the_passes_lower_for_the_tpu_at_published_widths():
    """What the chip will be asked: the decode program of Ouro-2.6B's
    configuration lowers with the 48-layer body traced ONCE (48
    ``paged_attn`` calls in one loop, not 192) and the pools of 192
    cache layers handed to the kernel as one run of pages."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro-2.6b-serve.json")) as f:
        doc = json.load(f)
    cfg = tfm.TransformerConfig(**doc["model"])
    sv = doc["serve"]
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    pool = jax.ShapeDtypeStruct(
        cfg.kv_pool_shapes(sv["n_blocks"], sv["block_size"])[0], cfg.jdtype)
    assert pool.shape == (192, 92, 64, 16, 128)
    rows = sv["max_active"]
    i32 = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        text = jax.jit(tfm.forward_decode_paged, static_argnums=(7,)).trace(
            shapes, i32, i32, pool, pool,
            jax.ShapeDtypeStruct((rows, 11), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32), cfg).lower(
                lowering_platforms=("tpu",)).as_text()
    # the kernel lowers once, into a function each layer calls
    kernel_fn, = [re.match(r" private @([\w.]+)\(", chunk).group(1)
                  for chunk in text.split("func.func")
                  if 'kernel_name = "paged_attn"' in chunk]
    assert text.count(f"call @{kernel_fn}(") == 48
    assert "stablehlo.while" in text
    assert "tensor<17664x64x16x128xbf16>" in text  # 192 x 92 pages


def test_the_counters_of_a_looped_decode(model):
    """One request alone: every decode step adds the K/V of each
    attended token in all nine cache layers; the first token comes from
    the prefill."""
    cfg, params, ids, _ = model
    before = telemetry.counters_snapshot().get("serving", {})
    n, m = 10, 8
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=BS,
                          max_active=2, queue_depth=4)
    eng.start()
    try:
        req = eng.submit(ids[0, :n].tolist(), m)
        assert req.wait(120) and req.error is None, req.error
    finally:
        eng.close()
    c = telemetry.counters_snapshot()["serving"]
    grew = {k: c[k] - before.get(k, 0) for k in (
        "paged_decode_steps", "loop_kv_read_bytes")}
    assert grew["paged_decode_steps"] == m - 1
    # step j of the m - 1 reads the n + j cached tokens and its own
    attended = sum(n + j + 1 for j in range(m - 1))
    assert grew["loop_kv_read_bytes"] == 2 * 9 * 4 * 16 * 4 * attended


def test_the_engine_serves_a_looped_model(model):
    """Through InferenceEngine: two requests at once and one alone give
    the same ids, and every emitted token is the reference's top pick
    teacher-forced (float32 on both sides)."""
    cfg, params, ids, _ = model
    prompts = [ids[0, :11].tolist(), ids[1, :7].tolist()]

    def generate(max_active):
        eng = InferenceEngine(params, cfg, n_blocks=24, block_size=BS,
                              max_active=max_active, queue_depth=4)
        eng.start()
        try:
            reqs = [eng.submit(p, 12) for p in prompts]
            for r in reqs:
                assert r.wait(120) and r.error is None, r.error
            return [list(r.generated) for r in reqs]
        finally:
            eng.close()

    together, alone = generate(2), generate(1)
    assert together == alone and all(len(g) == 12 for g in together)
    for prompt, out in zip(prompts, together):
        seq = np.asarray(prompt + out[:-1], np.int32)
        logits = np.asarray(ref.logits_at(
            params, np.pad(seq, (0, 24 - len(seq))),
            np.arange(len(prompt) - 1, len(seq)), spec=spec_of(cfg)))
        gaps = logits.max(-1) - logits[np.arange(12), out]
        assert gaps.max() < 1e-4


def test_the_reference_control_moves_the_logits(model):
    """Rounding the matmul operands to float8 moves the reference's
    logits by far more than float32 rounding does."""
    cfg, params, ids, want = model
    low = np.asarray(ref.logits_at(params, ids[0], np.arange(T),
                                   quantize=jnp.float8_e4m3fn,
                                   spec=spec_of(cfg)))
    assert np.abs(low - want[0]).max() > 1e-2


def test_reference_constants_equal_the_configuration_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro-2.6b-serve.json")) as f:
        doc = json.load(f)
    cfg = tfm.TransformerConfig(**doc["model"])
    assert ref.OURO == spec_of(cfg) == ref.Spec(
        passes=doc["total_ut_steps"], rope_theta=doc["rope_theta"],
        norm_eps=doc["rms_norm_eps"])
    published = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff, "num_hidden_layers": cfg.n_layers,
        "vocab_size": cfg.vocab, "total_ut_steps": cfg.loop_passes,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings}
    assert {k: doc[k] for k in published} == published
    assert doc["model_type"] == "ouro" and doc["hidden_act"] == "silu"
    assert doc["early_exit_threshold"] == 1 and doc["reduced"] == []
    assert doc["layer_types"] == ["full_attention"] * cfg.n_layers
    assert not doc["use_sliding_window"] and doc["sliding_window"] is None
    assert doc["rope_scaling"] is None
    assert cfg.sandwich_norm and cfg.family == "mha" and cfg.n_experts == 1
    assert doc["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    # the model as the configuration's notes reckon it: 51.39M a layer,
    # 2.668B in all,
    # 1,572,864 B of K/V a cached token, 9.26 GB of pools
    assert tfm.count_params(cfg) == 48 * 51388416 + 2 * 49152 * 2048 \
        + 2048 + 2049
    assert round(tfm.count_params(cfg) / 1e9, 3) == 2.668
    sv = doc["serve"]
    k_pool, v_pool = cfg.kv_pool_shapes(sv["n_blocks"], sv["block_size"])
    assert k_pool == v_pool == (192, 92, 64, 16, 128)
    per_token = 2 * 192 * 16 * 128 * 2
    assert per_token == 1572864
    assert round(sv["n_blocks"] * sv["block_size"] * per_token / 1e9, 2) \
        == 9.26
    assert "env" in sv and not sv["env"]
