"""Paged decode: parity with dense attention, spec-decode bit-parity.

The op-level matrix checks the paged attention op against dense
softmax attention written out in numpy on identical cache state — the
1e-5 parity contract, swept where a length matrix is cheapest.  The
engine-level tests pin the end-to-end contract instead: greedy outputs
bit-identical to the no-cache oracle, with and without speculative
decoding, each through a forced preemption episode (the resume path is
where a paged/spec bookkeeping bug would corrupt output).  The cache
tests guard its one residence, the device pools: freed blocks' bytes
never reach a live row, prefill writes a sequence's blocks inside the
program and nothing else, the constructors take no other residence,
and a prefill that fails fails alone unless it took the donated pools
with it.  The decode program owns its pools for the length of a call:
donated at the engine's jit site, never sliced by layer, dead rows
harmless in every layer, and a call that fails with the pools in its
hands is an iteration crash the loop recovers from.  The weights are
the engine's in the same way: held one array a layer and matrix, so no
program slices a stack, with the values and the ids the stack gives.
"""

import json
import urllib.request

import numpy as np
import pytest

from dmlc_tpu import telemetry
from dmlc_tpu.base import DMLCError
from dmlc_tpu.ops import paged_attention as paged_ops
from dmlc_tpu.ops.paged_attention import paged_attention, supports
from dmlc_tpu.serving import (InferenceEngine, PagedKVCache, Request,
                              ServingHTTPServer)
from kv_rows import kv_get, kv_put


# ---------------------------------------------------------------------------
# op-level parity matrix: paged vs dense window attention
# ---------------------------------------------------------------------------

def _rand(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


def _dense_window_attention(q, k_new, v_new, k_cache, v_cache, lengths):
    """Softmax attention in numpy, independent of the package: window
    token s of row b (q / k_new / v_new [B, S, H, D]) sees cache slots
    j < lengths[b] of k_cache / v_cache [B, Tc, H, D] and window tokens
    <= s, which ride as a tail behind the cache."""
    tc, s_w, d = k_cache.shape[1], q.shape[1], q.shape[-1]
    k_all = np.concatenate([k_cache, k_new], axis=1)
    v_all = np.concatenate([v_cache, v_new], axis=1)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k_all) / np.sqrt(d)
    idx = np.arange(tc + s_w)
    valid = (idx[None, None] < lengths[:, None, None]) | (
        (idx >= tc) & (idx - tc <= np.arange(s_w)[:, None]))[None]
    scores = np.where(valid[:, None], scores, -np.inf)
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", p / p.sum(axis=-1, keepdims=True),
                     v_all)


def _parity_case(rng, *, n_blocks, bs, w, h, d, s_w, lengths):
    """Build one batch of paged state plus its dense view.

    Returns ``(paged_out, dense_out)`` for the same queries: the paged
    op attends the scattered pool through block tables; the dense
    oracle sees the rows gathered through the same tables, with the
    window riding as a concatenated tail."""
    b = len(lengths)
    lengths = np.asarray(lengths, np.int32)
    span = w * bs
    k_pool = _rand(rng, n_blocks, bs, h, d)
    v_pool = _rand(rng, n_blocks, bs, h, d)
    # disjoint physical blocks per row (sequences never share blocks),
    # deliberately non-contiguous within each row
    assert n_blocks >= b * w
    tables = rng.permutation(n_blocks)[:b * w].reshape(b, w).astype(np.int32)
    q = _rand(rng, b, s_w, h, d)
    k_new = _rand(rng, b, s_w, h, d)
    v_new = _rand(rng, b, s_w, h, d)
    # paged: scatter-then-attend at each row's real paged address
    kp, vp = k_pool.copy(), v_pool.copy()
    for i in range(b):
        for s in range(s_w):
            p = int(lengths[i]) + s
            kp[tables[i, p // bs], p % bs] = k_new[i, s]
            vp[tables[i, p // bs], p % bs] = v_new[i, s]
    paged = np.asarray(paged_attention(q, kp, vp, tables, lengths,
                                       impl="lax"))
    # dense: the PRE-scatter pool is the cache (positions >= length
    # are garbage the mask hides), window as explicit tail
    k_cache = k_pool[tables].reshape(b, span, h, d)
    v_cache = v_pool[tables].reshape(b, span, h, d)
    dense = _dense_window_attention(q, k_new, v_new, k_cache, v_cache,
                                    lengths)
    return paged, dense


@pytest.mark.parametrize("s_w", [1, 3])
def test_paged_vs_gather_parity_matrix(s_w):
    """Single-block, boundary-straddling, and max-length rows in one
    batch: the paged op matches the dense oracle to 1e-5."""
    bs, w = 4, 4
    span = w * bs
    lengths = [1, bs - 1, bs, bs + 1, 2 * bs + 1, span - s_w]
    paged, dense = _parity_case(np.random.default_rng(0), n_blocks=24,
                                bs=bs, w=w, h=2, d=8, s_w=s_w,
                                lengths=lengths)
    np.testing.assert_allclose(paged, dense, rtol=1e-5, atol=1e-5)


def _page_lengths(bs, w, s_w, edge):
    """Lengths that sit on, before and after page boundaries, and one
    dead row."""
    return [1, bs - 1, bs, bs + 1, w * bs - s_w, 0]


def _block_lengths(bs, w, s_w, edge):
    """What a walk in blocks of ``edge`` positions adds: a row shorter
    than one block, one whose last attended position is a block's
    last, one a position (so a page) past that, a dead row before a
    row many blocks long, and the longest row last."""
    assert w * bs > 2 * edge and (w * bs) % edge, "W: no whole blocks"
    return [5, edge - s_w, edge - s_w + 1, 0, 2 * edge + bs + 3,
            w * bs - s_w]


@pytest.mark.parametrize("h,bs,s_w,dtype,tol,w,lengths_of", [
    (1, 8, 1, np.float32, 1e-5, 3, _page_lengths),
    (16, 16, 1, np.float32, 1e-5, 3, _page_lengths),  # flagship heads, page
    (16, 16, 4, np.float32, 1e-5, 3, _page_lengths),  # ... a verify window
    (16, 16, 4, "bfloat16", 2e-2, 3, _page_lengths),  # ... serving dtype
    # tables wider than two blocks and no multiple of one
    (16, 16, 1, np.float32, 1e-5, 38, _block_lengths),
    (16, 16, 3, np.float32, 1e-5, 38, _block_lengths),
    (16, 16, 1, "bfloat16", 2e-2, 70, _block_lengths),
    (16, 16, 4, "bfloat16", 2e-2, 70, _block_lengths),
])
def test_paged_attention_pallas_interpret_parity(h, bs, s_w, dtype, tol, w,
                                                 lengths_of):
    """The Pallas kernel (interpret mode on CPU) agrees with the lax
    reference on every live row (a dead row's output is garbage by
    contract, not compared)."""
    import jax.numpy as jnp

    d = 128
    assert supports(d, bs, h) == (h == 16)
    # the kernel's own block, from its shapes
    chunk, per_block = paged_ops._walk_shape(
        s_w * h, bs * h, d, jnp.dtype(dtype).itemsize, w)
    edge = chunk * per_block * bs
    lengths = lengths_of(bs, w, s_w, edge)
    rng = np.random.default_rng(1)
    n_blocks = len(lengths) * w
    k_pool = jnp.asarray(_rand(rng, n_blocks, bs, h, d), dtype)
    v_pool = jnp.asarray(_rand(rng, n_blocks, bs, h, d), dtype)
    tables = rng.permutation(n_blocks).reshape(-1, w).astype(np.int32)
    q = jnp.asarray(_rand(rng, len(lengths), s_w, h, d), dtype)
    lens = np.asarray(lengths, np.int32)
    live = lens > 0
    ref = np.asarray(paged_attention(q, k_pool, v_pool, tables, lens,
                                     impl="lax"), np.float32)
    got = np.asarray(paged_attention(q, k_pool, v_pool, tables, lens,
                                     impl="pallas", interpret=True),
                     np.float32)
    np.testing.assert_allclose(got[live], ref[live], rtol=tol, atol=tol)


@pytest.mark.parametrize("s_w", [1, 3])
def test_paged_attention_pallas_reads_only_named_pages(s_w):
    """Every page that no live row's first ``ceil((length + S) / bs)``
    table entries name holds NaN: the padded entries' page 0 (a dead
    row's whole table), the pages of a table's tail a row has not
    grown into, the pool's free pages.  The kernel's output is finite
    and is the lax twin's on the clean pool (the twin gathers every
    entry, and 0 x NaN is NaN)."""
    import jax.numpy as jnp

    h, bs, d, w = 16, 16, 128, 9
    lengths = [3, 0, 2 * bs, 5 * bs + 7, 0, w * bs - s_w]
    rng = np.random.default_rng(2)
    n_blocks = len(lengths) * w + 5
    lens = np.asarray(lengths, np.int32)
    tables = np.zeros((len(lengths), w), np.int32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    named = []
    for i, n in enumerate(lens):
        pages = -(-(int(n) + s_w) // bs) if n else 0
        tables[i, :pages] = [free.pop() for _ in range(pages)]
        named.extend(tables[i, :pages])
    clean_k = _rand(rng, n_blocks, bs, h, d)
    clean_v = _rand(rng, n_blocks, bs, h, d)
    poison = np.ones(n_blocks, bool)
    poison[named] = False
    assert poison[0] and poison.sum() > 5
    k_pool, v_pool = clean_k.copy(), clean_v.copy()
    k_pool[poison] = np.nan
    v_pool[poison] = np.nan
    q = jnp.asarray(_rand(rng, len(lengths), s_w, h, d))
    live = lens > 0
    ref = np.asarray(paged_attention(q, clean_k, clean_v, tables, lens,
                                     impl="lax"))
    got = np.asarray(paged_attention(q, jnp.asarray(k_pool),
                                     jnp.asarray(v_pool), tables, lens,
                                     impl="pallas", interpret=True))
    assert np.isfinite(got[live]).all()
    np.testing.assert_allclose(got[live], ref[live], rtol=1e-5, atol=1e-5)


def test_paged_attention_rejects_unknown_impl():
    z = np.zeros((1, 1, 1, 8), np.float32)
    pool = np.zeros((2, 4, 1, 8), np.float32)
    with pytest.raises(ValueError):
        paged_attention(z, pool, pool, np.zeros((1, 2), np.int32),
                        np.zeros((1,), np.int32), impl="cuda")


# ---------------------------------------------------------------------------
# the one residence: device pools
# ---------------------------------------------------------------------------

def _kv(rng, n, *, layers=2, heads=2, dim=3):
    shape = (layers, n, heads, dim)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def test_live_rows_never_read_freed_blocks_bytes():
    """Property: under interleaved alloc/free churn, a live sequence's
    pages never hold a freed block's bytes.  Every free block is
    poisoned with a sentinel each iteration; any table indexing bug
    that routed a live row through a freed block would surface it."""
    sent = np.float32(12345.0)
    cache = PagedKVCache(2, 2, 3, n_blocks=12, block_size=4)
    rng = np.random.default_rng(11)
    live, sid = {}, 0
    for _ in range(60):
        if live and (len(live) >= 4 or rng.random() < 0.5):
            victim = int(rng.choice(sorted(live)))
            cache.free(victim)
            del live[victim]
        else:
            sid += 1
            n = int(rng.integers(1, 13))
            if cache.allocate(sid, n):
                k, v = _kv(rng, n)
                kv_put(cache, sid, k, v)
                live[sid] = (n, k, v)
        used = set()
        for s in live:
            used.update(cache.block_table(s))
        freed = np.array(sorted(set(range(12)) - used), np.int32)
        cache.adopt_device_pools(*(
            p.at[:, freed].set(sent) for p in cache.device_pools()))
        if not live:
            continue
        ids = sorted(live)
        gk, gv, lens = kv_get(cache, ids, pad_batch=len(ids) + 2)
        for row, s in enumerate(ids):
            n, k, v = live[s]
            assert lens[row] == n
            np.testing.assert_array_equal(gk[:, row, :n], k)
            np.testing.assert_array_equal(gv[:, row, :n], v)
        # dead pad rows sit behind length 0
        assert not lens[len(ids):].any()


def test_the_cache_keeps_its_kv_in_device_pools():
    """The one residence's contract: pools made as zeros on the device
    at first use, lengths moved by advance_many alone and never past
    the reservation, lost pools forgotten and made anew."""
    import jax

    cache = PagedKVCache(2, 2, 3, n_blocks=4, block_size=4)
    assert cache.allocate(1, 3)
    k_pool, v_pool = cache.device_pools()
    assert isinstance(k_pool, jax.Array) and isinstance(v_pool, jax.Array)
    assert k_pool.shape == v_pool.shape == (2, 4, 4, 2, 3)
    assert not np.asarray(k_pool).any()  # made as zeros, on the device
    assert cache.device_pools()[0] is k_pool  # and kept
    assert cache.length(1) == 0
    cache.advance_many([(1, 3)])
    assert cache.length(1) == 3 and cache.stats()["cached_tokens"] == 3
    with pytest.raises(DMLCError, match="past reservation"):
        cache.advance_many([(1, 2)])  # 5 tokens in a 1-block reservation
    assert cache.length(1) == 3
    assert not cache.drop_lost_pools()  # intact pools are kept
    k_pool.delete()
    assert cache.drop_lost_pools()
    assert not any(p.is_deleted() for p in cache.device_pools())


@pytest.mark.parametrize("build", [InferenceEngine, PagedKVCache])
def test_serving_constructors_choose_no_residence(build):
    """Where the cache's bytes live is not a parameter: no keyword
    names a residence or a mesh to shard a host-side view over, and
    one passed anyway is refused."""
    import inspect

    names = inspect.signature(build.__init__).parameters
    assert not [n for n in names if "mesh" in n or "resident" in n]
    args = _tiny_model() if build is InferenceEngine else (2, 2, 3)
    with pytest.raises(TypeError, match="mesh"):
        build(*args, n_blocks=4, block_size=4, mesh=None)


def test_knob_count_only_goes_down():
    """ROADMAP C3.  The knob that chose between two serving data paths
    went with the second path: a PR that adds a knob argues with this
    line."""
    from dmlc_tpu.config_registry import KNOBS

    assert not [k.name for k in KNOBS if "PAGED" in k.name]
    assert len(KNOBS) <= 164
    assert sum(k.name.startswith("DMLC_SERVE_") for k in KNOBS) <= 18


@pytest.mark.parametrize("n", [8, 6])  # n % block_size == 0 and != 0
def test_paged_prefill_writes_exactly_the_sequences_blocks(n):
    """After a paged prefill the pool blocks of the sequence's table
    hold exactly the k, v that forward_prefill_last returns for [:n],
    the logits are the same, and no other block changed."""
    import jax

    from dmlc_tpu.models import transformer as tfm

    params, cfg = _tiny_model()
    bs, n_blocks = 4, 7
    padded = n + (-n % bs)
    ids = np.zeros((1, padded), np.int32)
    ids[0, :n] = np.arange(1, n + 1)
    last = np.array([n - 1], np.int32)
    want_logits, want_k, want_v = jax.jit(
        tfm.forward_prefill_last, static_argnums=(3,))(params, ids, last,
                                                       cfg)
    rng = np.random.default_rng(n)
    shape = (cfg.n_layers, n_blocks, bs, cfg.n_heads, cfg.head_dim)
    k0, v0 = _rand(rng, *shape), _rand(rng, *shape)
    table = np.array([5, 2], np.int32)  # neither contiguous nor ordered
    prog = jax.jit(tfm.forward_prefill_paged, static_argnums=(6,),
                   donate_argnums=(3, 4))  # as the engine compiles it
    logits, k_pool, v_pool = prog(params, ids, last, jax.numpy.asarray(k0),
                                  jax.numpy.asarray(v0), table, cfg)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    for got, want, before in ((np.asarray(k_pool), np.asarray(want_k), k0),
                              (np.asarray(v_pool), np.asarray(want_v), v0)):
        seq = got[:, table].reshape(cfg.n_layers, padded, cfg.n_heads,
                                    cfg.head_dim)
        np.testing.assert_array_equal(seq[:, :n], want[:, 0, :n])
        others = np.setdiff1d(np.arange(n_blocks), table)
        np.testing.assert_array_equal(got[:, others], before[:, others])


@pytest.mark.parametrize("pools_lost", [False, True])
def test_failed_prefill_fails_alone_unless_it_took_the_pools(pools_lost):
    """An injected prefill failure fails its own request.  Raised
    before the donated pools were given up, nothing else notices; raised
    after (the arrays the cache holds are deleted), the engine treats
    it as an iteration crash: the live request is requeued, re-prefilled
    into fresh pools, and still emits the oracle's tokens."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=4,
                          max_active=2, queue_depth=4)
    real = eng._prefill
    poison = 63

    def prefill(p, ids, last, k_pool, v_pool, blocks, c):
        if ids[0, 0] == poison:
            if pools_lost:
                k_pool.delete()  # what a donating call that raised after
                v_pool.delete()  # dispatch leaves behind
            raise RuntimeError("injected prefill failure")
        return real(p, ids, last, k_pool, v_pool, blocks, c)

    eng._prefill = prefill
    before = telemetry.counters_snapshot().get("serving", {}).get(
        "crash_requeues", 0)
    eng.start()
    try:
        # 40 tokens: on a loaded host 12 were generated before the
        # poisoned prompt's turn came, one run in three
        good = eng.submit([1, 2, 3, 4, 5], max_new_tokens=40)
        while good.n_generated < 3:  # live, with K/V in the pools
            assert not good.wait(0.005)
        bad = eng.submit([poison, 1, 2], max_new_tokens=4)
        assert bad.wait(300) and good.wait(300)
    finally:
        eng.close()
    assert bad.error is not None and "prefill failed" in bad.error
    assert good.error is None
    assert good.generated == _greedy_oracle(params, cfg, [1, 2, 3, 4, 5], 40)
    after = telemetry.counters_snapshot()["serving"].get("crash_requeues", 0)
    assert after - before == (1 if pools_lost else 0)
    assert eng.cache.n_blocks_in_use == 0


# ---------------------------------------------------------------------------
# the decode program owns its pools: donated, never sliced by layer
# ---------------------------------------------------------------------------

def _decode_branch(branch):
    """``(params, cfg, kernel mode)`` for one branch of the decode
    program's attention: "lax" on the tiny model, "kernel" (the Pallas
    kernel, interpreted) on two layers at the flagship's page (16 heads
    x 128), the smallest the kernel serves."""
    import jax

    from dmlc_tpu.models import transformer as tfm
    from dmlc_tpu.ops import dispatch

    if branch == "lax":
        return _tiny_model() + (dispatch.LAX,)
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=16,
                                head_dim=128, d_ff=64, n_layers=2,
                                n_experts=1, microbatches=1)
    return (tfm.init_params(jax.random.PRNGKey(0), cfg), cfg,
            dispatch.INTERPRET)


def test_engine_decode_program_donates_both_pools():
    """The MHA decode program as the engine jits it marks both pools
    donated in its lowering; a step deletes the arrays the cache held
    and leaves it holding what the program returned."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=4,
                          max_active=2, queue_depth=4)
    returned = []
    real = eng._decode

    def decode(*a):
        out = real(*a)
        returned.append(out[2:4])
        return out

    eng._decode = decode
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    eng.step()  # the prefill, and the first decode window with it
    assert len(returned) == 1
    for _ in range(2):
        held = eng.cache.device_pools()
        assert not any(p.is_deleted() for p in held)
        eng.step()  # one decode window
        assert all(p.is_deleted() for p in held)
        now = eng.cache.device_pools()
        assert all(a is b for a, b in zip(now, returned[-1]))
        assert not any(p.is_deleted() for p in now)
    assert len(returned) == 3
    ids = np.zeros((2, 1), np.int32)
    lowered = getattr(real, "_jit", real).lower(
        params, (ids, ids, np.zeros((2,), np.int32)), ids,
        *eng.cache.device_pools(),
        np.zeros((2, 2), np.int32), np.zeros((2,), np.int32), cfg)
    import jax

    donated = [a.donated
               for a in jax.tree.leaves(lowered.args_info[0][1:])]
    assert donated == [False] * 4 + [True, True, False, False]
    assert lowered.as_text().count("tf.aliasing_output") == 2


def _shapes_in(jaxpr, seen):
    """Every intermediate's shape, sub-jaxprs (pjit, scan, cond, the
    kernel's body) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            seen.add(tuple(getattr(v.aval, "shape", ())))
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _shapes_in(sub, seen)
    return seen


@pytest.mark.parametrize("branch", ["lax", "kernel"])
def test_decode_program_never_makes_one_layers_pool(branch):
    """No intermediate of the traced forward_decode_paged has one
    layer's pool shape, as 4-D pages or as the kernel's 3-D view: a
    per-layer slice is a copy of the layer's pool at every call."""
    import jax

    from dmlc_tpu.models import transformer as tfm
    from dmlc_tpu.ops import dispatch

    params, cfg, mode = _decode_branch(branch)
    n_blocks, bs, b, w = 7, 16, 3, 2
    h, d = cfg.n_heads, cfg.head_dim
    pool = jax.ShapeDtypeStruct((cfg.n_layers, n_blocks, bs, h, d),
                                np.float32)
    ids = np.zeros((b, 1), np.int32)
    with dispatch.force_kernel_mode(mode):
        traced = jax.make_jaxpr(tfm.forward_decode_paged,
                                static_argnums=(7,))(
            params, ids, ids, pool, pool, np.zeros((b, w), np.int32),
            np.zeros((b,), np.int32), cfg)
    seen = _shapes_in(traced.jaxpr, set())
    assert (cfg.n_layers * n_blocks, bs, h, d) in seen  # the flat view
    assert ((cfg.n_layers * n_blocks, bs * h, d) in seen) == (
        branch == "kernel")
    assert (n_blocks, bs, h, d) not in seen
    assert (n_blocks, bs * h, d) not in seen
    assert (1, n_blocks, bs, h, d) not in seen


@pytest.mark.parametrize("branch", ["lax", "kernel"])
def test_dead_rows_touch_no_page_of_any_layer(branch):
    """One live row between two dead ones (length 0, table all zeros):
    in every layer the step writes the live row's one slot and nothing
    else.  Block 0 of the NEXT layer is the page a dead row's dropped
    scatter would hit if it were addressed in the kernel's flat view."""
    import jax.numpy as jnp

    from dmlc_tpu.models import transformer as tfm
    from dmlc_tpu.ops import dispatch

    params, cfg, mode = _decode_branch(branch)
    n_blocks, bs = 5, 16
    shape = (cfg.n_layers, n_blocks, bs, cfg.n_heads, cfg.head_dim)
    rng = np.random.default_rng(7)
    k0, v0 = _rand(rng, *shape), _rand(rng, *shape)
    tables = np.array([[0, 0], [3, 1], [0, 0]], np.int32)
    lengths = np.array([0, bs + 1, 0], np.int32)  # live: block 1, slot 1
    ids = np.array([[5], [9], [0]], np.int32)
    positions = lengths[:, None]
    with dispatch.force_kernel_mode(mode):
        logits, k1, v1 = tfm.forward_decode_paged(
            params, ids, positions, jnp.asarray(k0), jnp.asarray(v0),
            tables, lengths, cfg)
    assert np.isfinite(np.asarray(logits[1])).all()
    written = np.zeros(shape[:3], bool)
    written[:, 1, 1] = True
    for before, after in ((k0, np.asarray(k1)), (v0, np.asarray(v1))):
        np.testing.assert_array_equal(after[~written], before[~written])
        changed = (after[written] != before[written]).reshape(
            cfg.n_layers, -1)
        assert changed.any(axis=1).all()  # each layer wrote its slot


def test_failed_decode_that_took_the_pools_is_recovered():
    """A decode call that raises after dispatch has taken the donated
    pools with it: the live requests are requeued, re-prefilled into
    fresh pools and finish with the ids of an undisturbed run."""
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=4,
                          max_active=2, queue_depth=4)
    real = eng._decode
    calls = {"n": 0, "lost": None}

    def decode(p, ids, positions, k_pool, v_pool, tables, lengths, c):
        calls["n"] += 1
        if calls["n"] == 3:
            k_pool.delete()  # what a donating call that raised after
            v_pool.delete()  # dispatch leaves behind
            calls["lost"] = (k_pool, v_pool)
            raise RuntimeError("injected decode failure")
        return real(p, ids, positions, k_pool, v_pool, tables, lengths, c)

    eng._decode = decode
    before = telemetry.counters_snapshot().get("serving", {}).get(
        "crash_requeues", 0)
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
    finally:
        eng.close()
    assert calls["lost"] is not None and calls["n"] > 3
    for r, p in zip(reqs, prompts):
        assert r.error is None
        assert r.generated == _greedy_oracle(params, cfg, p, 10)
    after = telemetry.counters_snapshot()["serving"].get("crash_requeues", 0)
    assert after - before >= 1  # every request that was live went back
    fresh = eng.cache.device_pools()
    assert not any(p.is_deleted() for p in fresh)
    assert all(a is not b for a, b in zip(fresh, calls["lost"]))
    assert eng.cache.n_blocks_in_use == 0


# ---------------------------------------------------------------------------
# the engine holds the weights as its programs read them: one array a
# layer and matrix, so no program takes a layer's slice out of a stack
# ---------------------------------------------------------------------------

def _family_toy(family):
    """``(params, cfg)`` of a small model of one family: the flagship's
    stacked tree, or another family's own test module's."""
    import importlib

    if family == "mha":
        return _tiny_model()
    mod = importlib.import_module(
        {"mha_swa": "test_cohere2_family", "mla": "test_latent_family",
         "kda_mla": "test_hybrid_family"}[family])
    cfg = mod.small()
    assert cfg.family == family
    return mod.weights(cfg), cfg


def _slices_of_weights(program, n_weights, *args):
    """The ``slice`` / ``dynamic_slice`` equations of the traced
    ``program(*args)`` whose operand is one of the program's first
    ``n_weights`` arguments (the leaves of ``params``), followed through
    the calls that pass them on whole."""
    found = []

    def walk(jaxpr, weights):  # weights: ids (a literal has no hash)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("slice", "dynamic_slice") \
                    and id(eqn.invars[0]) in weights:
                found.append(eqn)
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", val)
                if hasattr(sub, "eqns") \
                        and len(sub.invars) == len(eqn.invars):
                    walk(sub, {id(inner) for inner, outer in zip(
                        sub.invars, eqn.invars) if id(outer) in weights})

    jaxpr = program.trace(*args).jaxpr.jaxpr
    walk(jaxpr, {id(v) for v in jaxpr.invars[:n_weights]})
    return found


def test_engine_programs_slice_no_weight_argument():
    """Neither program of an engine built on a stacked tree takes a
    slice of a weight it was handed; the same programs handed the stack
    take one a layer and matrix, which XLA is free to copy (403 MB a
    decode step at the flagship's widths)."""
    import jax

    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=4,
                          max_active=2, queue_depth=4)
    prefill = getattr(eng._prefill, "_jit", eng._prefill)
    decode = getattr(eng._decode, "_jit", eng._decode)
    pools = eng.cache.device_pools()
    ids = np.zeros((2, 1), np.int32)
    for tree, sliced in ((eng.params, False), (params, True)):
        n = len(jax.tree.leaves(tree))
        calls = (
            (prefill, tree, np.zeros((1, 8), np.int32),
             np.zeros((1,), np.int32), *pools, np.zeros((2,), np.int32),
             cfg),
            (decode, tree, (ids, ids, np.zeros((2,), np.int32)), ids,
             *pools, np.zeros((2, 2), np.int32), np.zeros((2,), np.int32),
             cfg))
        for program, *args in calls:
            found = _slices_of_weights(program, n, *args)
            # ten matrices in each of the toy's two layers
            assert len(found) == (10 * cfg.n_layers if sliced else 0)


def _paged_greedy(params, cfg, prompt, n_new, bs=16, n_blocks=4):
    """Greedy decode of one prompt through forward_prefill_paged and
    forward_decode_paged called as they are, untraced: ``(ids, the
    logits of every pick)``."""
    import jax.numpy as jnp

    from dmlc_tpu.models import transformer as tfm

    shape = (cfg.n_layers, n_blocks, bs, cfg.kv_heads, cfg.head_dim)
    k_pool, v_pool = jnp.zeros(shape), jnp.zeros(shape)
    n = len(prompt)
    padded = -(-n // bs) * bs
    assert n + n_new <= n_blocks * bs
    row, k_pool, v_pool = tfm.forward_prefill_paged(
        params, np.array([prompt + [0] * (padded - n)], np.int32),
        np.array([n - 1], np.int32), k_pool, v_pool,
        np.arange(padded // bs, dtype=np.int32), cfg)
    table = np.arange(n_blocks, dtype=np.int32)[None]
    ids, logits = [], [np.asarray(row[0])]
    for length in range(n, n + n_new - 1):
        ids.append(int(np.argmax(logits[-1])))
        row, k_pool, v_pool = tfm.forward_decode_paged(
            params, np.array([[ids[-1]]], np.int32),
            np.array([[length]], np.int32), k_pool, v_pool, table,
            np.array([length], np.int32), cfg)
        logits.append(np.asarray(row[0, 0]))
    ids.append(int(np.argmax(logits[-1])))
    return ids, np.stack(logits)


@pytest.mark.parametrize("branch", ["lax", "kernel"])
def test_both_trees_give_the_same_logits_and_the_engine_their_ids(branch):
    """The per-layer tree is the stack's values in other arrays: the
    programs give the same logits from either, bit for bit, and an
    engine built on either emits the ids the stack's logits pick."""
    import dataclasses

    from dmlc_tpu.models import transformer as tfm
    from dmlc_tpu.ops import dispatch

    params, cfg, mode = _decode_branch(branch)
    # a vocabulary no other test has: the engine's jitted programs are
    # shared process-wide, and one traced under another mode would be
    # found again
    cfg = dataclasses.replace(cfg, vocab=cfg.vocab - 8)
    params = {**params, "embed": params["embed"][:cfg.vocab],
              "unembed": params["unembed"][:, :cfg.vocab]}
    layered = tfm.per_layer_params(params)
    prompt, n_new = [3, 1, 4, 1, 5, 9, 2], 12
    with dispatch.force_kernel_mode(mode):
        ids, logits = _paged_greedy(params, cfg, prompt, n_new)
        ids_layered, logits_layered = _paged_greedy(
            layered, cfg, prompt, n_new)
        np.testing.assert_array_equal(logits_layered, logits)
        assert ids_layered == ids
        for tree in (params, layered):
            eng = InferenceEngine(tree, cfg, n_blocks=8, block_size=16,
                                  max_active=2, queue_depth=4)
            req = eng.submit(prompt, max_new_tokens=n_new)
            for _ in range(4 * n_new):
                if req.wait(0):
                    break
                eng.step()
            assert req.error is None and list(req.generated) == ids


@pytest.mark.parametrize("family", ["mha", "mha_swa"])
def test_mha_layers_yields_the_same_layers_from_either_tree(family):
    """``_mha_layers`` over the stack and over what ``per_layer_params``
    makes of it yields equal dicts; a tree that has ``layers`` (here
    beside its one stack of held experts) is converted to itself and
    yields each layer's own arrays with the experts' beside them."""
    from dmlc_tpu.models import transformer as tfm

    params, cfg = _family_toy(family)
    layered = tfm.per_layer_params(params)
    walked = list(tfm._mha_layers(layered, cfg))
    assert [kind for kind, _, _ in walked] == list(cfg.layer_kinds)
    if family == "mha":
        assert "blocks" not in layered and set(layered) - {"layers"} == (
            set(params) - {"blocks"})
        for (kind, p, group), (kind_s, p_s, group_s) in zip(
                walked, tfm._mha_layers(params, cfg), strict=True):
            assert (kind, group) == (kind_s, group_s) == ("mha", None)
            assert p.keys() == p_s.keys() == params["blocks"].keys()
            for name in p:
                np.testing.assert_array_equal(p[name], p_s[name])
    else:
        assert layered is params
        for i, (_, p, group) in enumerate(walked):
            assert group == i * cfg.n_experts
            assert p.keys() == (params["layers"][i].keys()
                                | params["experts"].keys())
            for name, a in p.items():
                assert a is params["layers"][i].get(
                    name, params["experts"].get(name))


@pytest.mark.parametrize("family", ["mha", "mha_swa", "mla", "kda_mla"])
def test_the_tree_an_engine_holds(family):
    """An MHA engine handed the stack holds one array a layer and
    matrix and nothing with the stack's leading axes; every other tree
    is held as the very object that came in."""
    import jax

    params, cfg = _family_toy(family)
    eng = InferenceEngine(params, cfg, n_blocks=16, block_size=8,
                          max_active=2, queue_depth=4)
    if family != "mha":
        assert eng.params is params
        return
    lead = params["blocks"]["ln1"].shape[:2]
    assert lead == (1, cfg.n_layers)
    assert "blocks" not in eng.params
    assert len(eng.params["layers"]) == cfg.n_layers
    for name in ("embed", "unembed", "ln_f"):
        assert eng.params[name] is params[name]
    stacked = {a.shape for a in jax.tree.leaves(params["blocks"])}
    for a in jax.tree.leaves(eng.params["layers"]):
        assert a.shape[:2] != lead and (lead + a.shape) in stacked
    again = InferenceEngine(eng.params, cfg, n_blocks=16, block_size=8,
                            max_active=2, queue_depth=4)
    assert again.params is eng.params


# ---------------------------------------------------------------------------
# engine-level bit-parity (real jitted compute, tiny config)
# ---------------------------------------------------------------------------

def _tiny_model():
    import jax

    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2, head_dim=8,
                                d_ff=64, n_layers=2, n_experts=1,
                                microbatches=1)
    return tfm.init_params(jax.random.PRNGKey(0), cfg), cfg


def _greedy_oracle(params, cfg, prompt, n):
    from dmlc_tpu.models import transformer as tfm

    ctx = list(prompt)
    for _ in range(n):
        lg, _, _ = tfm.forward_prefill(
            params, np.array([ctx], np.int32), cfg)
        ctx.append(int(np.argmax(np.asarray(lg[0, -1]))))
    return ctx[len(prompt):]


def _prompt(i):
    """Request i's prompt: 4, 5 and 6 tokens over blocks of 4, so a
    prefill ends on a block boundary and inside a block, and every
    request decodes across one."""
    return [i + 1] * (4 + i)


def _run_requests(params, cfg, *, n_blocks=6, max_new=10):
    """3 requests through a pool too small for them to coexist: forces
    preemption + recompute-resume.  Returns their outputs."""
    eng = InferenceEngine(params, cfg, n_blocks=n_blocks, block_size=4,
                          max_active=3, queue_depth=8)
    eng.start()
    try:
        reqs = [eng.submit(_prompt(i), max_new_tokens=max_new)
                for i in range(3)]
        for r in reqs:
            assert r.wait(300), f"request {r.id} never finished"
            assert r.error is None
            assert r.n_generated == max_new
        return [list(r.generated) for r in reqs]
    finally:
        eng.close()


def test_paged_decode_bit_identical_to_oracle_through_preemption():
    """Greedy output across a preemption episode matches the no-cache
    oracle bit for bit — the paged cache is output-invisible end to
    end."""
    params, cfg = _tiny_model()
    before = telemetry.snapshot()["counters"].get(
        "serving", {}).get("preemptions", 0)
    outs = _run_requests(params, cfg)
    after = telemetry.snapshot()["counters"]["serving"]["preemptions"]
    assert after > before, "tiny pool must have forced preemption"
    for i in range(3):
        assert outs[i] == _greedy_oracle(params, cfg, _prompt(i), 10)


def test_spec_decode_bit_parity_through_preemption(monkeypatch):
    """Speculative decoding (k=3) through the same preemption-forcing
    pool (the commit advances each length by the accepted count;
    rejected window slots stay garbage in the device pool): greedy
    output stays bit-identical to the oracle, and the drafter actually
    proposed (the accept walk, not drafter silence, is what kept the
    output exact)."""
    params, cfg = _tiny_model()
    monkeypatch.setenv("DMLC_SERVE_SPEC_K", "3")
    monkeypatch.setenv("DMLC_SERVE_SPEC_MIN_CTX", "4")
    snap = telemetry.snapshot()["counters"].get("serving", {})
    before_prop = snap.get("spec_proposed", 0)
    before_acc = snap.get("spec_accepted", 0)
    before_pre = snap.get("preemptions", 0)
    outs = _run_requests(params, cfg, max_new=12)
    counters = telemetry.snapshot()["counters"]["serving"]
    assert counters.get("spec_proposed", 0) > before_prop, \
        "drafter never proposed — the spec path was not exercised"
    assert counters.get("spec_accepted", 0) > before_acc, \
        "no draft accepted — no commit advanced a length by more than one"
    assert counters["preemptions"] > before_pre
    for i in range(3):
        assert outs[i] == _greedy_oracle(params, cfg, _prompt(i), 12)


def test_ngram_drafter_proposes_from_own_context(monkeypatch):
    monkeypatch.setenv("DMLC_SERVE_SPEC_K", "3")
    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=8, block_size=4,
                          max_active=2, queue_depth=4)
    try:
        # rightmost fully-in-prefix occurrence of suffix [3,1,2] is at
        # offset 2, so the drafter replays what followed it
        assert eng._draft_tokens(
            Request([1, 2, 3, 1, 2, 3, 1, 2], 4)) == [3, 1, 2]
        # below DMLC_SERVE_SPEC_MIN_CTX (default 4): no proposal
        assert eng._draft_tokens(Request([1, 2], 4)) == []
        # no recurring suffix anywhere: no proposal
        assert eng._draft_tokens(Request([1, 2, 3, 4, 5, 6, 7], 4)) == []
    finally:
        eng.close()


def test_fast_path_metric_families_registered():
    from dmlc_tpu.telemetry.metric_names import METRIC_NAMES

    for fam in ("dmlc_serving_paged_decode_steps",
                "dmlc_serving_spec_proposed",
                "dmlc_serving_spec_accepted",
                "dmlc_serving_spec_accept_rate",
                "dmlc_serving_spec_tokens_per_step",
                "dmlc_step_spec_accept_rate_pct"):
        assert fam in METRIC_NAMES, f"{fam} missing from metric registry"


# ---------------------------------------------------------------------------
# loadgen CLI (the out-of-process bench driver)
# ---------------------------------------------------------------------------

def test_loadgen_cli_drives_server_and_emits_summary(capsys):
    from dmlc_tpu.serving.loadgen import _cli

    params, cfg = _tiny_model()
    eng = InferenceEngine(params, cfg, n_blocks=32, block_size=4,
                          max_active=2, queue_depth=8)
    eng.start()
    srv = ServingHTTPServer(eng, port=0)
    try:
        rc = _cli(["--url", srv.url, "--streams", "2",
                   "--requests-per-stream", "1", "--prompt-len", "2", "4",
                   "--max-tokens", "3", "--vocab", str(cfg.vocab)])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["n_requests_ok"] == 2 and doc["n_requests_failed"] == 0
        assert doc["failures"] == []
        # the server really served them
        reqs = json.loads(urllib.request.urlopen(
            srv.url + "/requests", timeout=30).read())
        assert reqs["summary"]["requests_done"] >= 2
    finally:
        srv.close()
        eng.close()
