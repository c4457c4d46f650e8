"""Tests for the parallelism layer on an 8-device virtual CPU mesh.

Covers mesh factorization, the collective surface, ring attention vs the
unsharded oracle, Ulysses all-to-all attention, and the SPMD pipeline —
the multi-chip machinery the reference delegated to rabit/ps-lite
(SURVEY.md §2.7), rebuilt on XLA collectives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dmlc_tpu.parallel import (
    all_gather,
    all_reduce,
    all_to_all,
    broadcast,
    build_mesh,
    factorize_devices,
    pipeline,
    ppermute_ring,
    reduce_scatter,
    ring_attention_reference,
    ulysses_attention,
)
from dmlc_tpu.parallel.mesh import MESH_AXES, mesh_config
from dmlc_tpu.parallel.ring_attention import make_sharded_ring_attention


def test_factorize_exact():
    shape = factorize_devices(8)
    assert np.prod(list(shape.values())) == 8
    assert shape["tp"] == 2 and shape["sp"] == 2 and shape["pp"] == 2
    shape = factorize_devices(8, tp=4, pp=1)
    assert shape["tp"] == 4
    with pytest.raises(ValueError):
        factorize_devices(8, tp=3)


def test_build_mesh_and_part_contract():
    mesh = build_mesh(8)
    assert mesh.axis_names == MESH_AXES
    cfg = mesh_config(mesh)
    assert cfg.n_devices == 8
    assert cfg.data_parts == cfg.axis_size("dp") * cfg.axis_size("sp")
    # part_index enumerates (dp, sp) row-major
    seen = set()
    for d in range(cfg.axis_size("dp")):
        for s in range(cfg.axis_size("sp")):
            seen.add(cfg.part_index({"dp": d, "sp": s}))
    assert seen == set(range(cfg.data_parts))


@pytest.fixture(scope="module")
def mesh8():
    return build_mesh(8, tp=1, sp=8, pp=1)  # one flat ring for collective tests


def _smap(mesh, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def test_collectives_numerics(mesh8):
    x = jnp.arange(8.0)

    out = _smap(mesh8, lambda v: all_reduce(v, "sp"), (P("sp"),), P("sp"))(x)
    np.testing.assert_allclose(out, np.full(8, 28.0))

    out = _smap(mesh8, lambda v: all_gather(v, "sp"), (P("sp"),), P("sp"))(x)
    assert out.shape == (64,)
    np.testing.assert_allclose(out[:8], np.arange(8.0))

    out = _smap(mesh8, lambda v: reduce_scatter(v, "sp"), (P(None),), P("sp"))(
        jnp.ones(8)
    )
    np.testing.assert_allclose(out, np.full(8, 8.0))

    out = _smap(mesh8, lambda v: broadcast(v, "sp", root=3), (P("sp"),), P("sp"))(x)
    np.testing.assert_allclose(out, np.full(8, 3.0))

    out = _smap(mesh8, lambda v: ppermute_ring(v, "sp", 1), (P("sp"),), P("sp"))(x)
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_all_to_all(mesh8):
    # a2a re-shards rows→columns: rank i starts with row i ([1,8]) and ends
    # with column i ([8,1]); the global value is unchanged.
    x = jnp.arange(64.0).reshape(8, 8)
    out = _smap(
        mesh8,
        lambda v: all_to_all(v, "sp", split_axis=1, concat_axis=0),
        (P("sp", None),),
        P(None, "sp"),
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = build_mesh(8, sp=4, tp=2, pp=1, dp=1)
    b, t, h, d = 2, 32, 4, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, d), jnp.float32)

    want = ring_attention_reference(q, k, v, causal=causal)
    fn = make_sharded_ring_attention(mesh, causal=causal)
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ring_attention_grads_flow():
    mesh = build_mesh(8, sp=4, tp=2, pp=1, dp=1)
    b, t, h, d = 1, 16, 2, 4
    q = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, d))
    fn = make_sharded_ring_attention(mesh, causal=True)

    def loss(q):
        return jnp.sum(fn(q, q, q) ** 2)

    def loss_ref(q):
        return jnp.sum(ring_attention_reference(q, q, q, causal=True) ** 2)

    g = jax.grad(loss)(q)
    g_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)


def test_ulysses_matches_reference():
    # local heads (h/tp = 4) must be divisible by sp (4) for the a2a re-shard
    mesh = build_mesh(8, sp=4, tp=2, pp=1, dp=1)
    b, t, h, d = 2, 32, 8, 8
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, h, d))
    v = jax.random.normal(kv, (b, t, h, d))
    want = ring_attention_reference(q, k, v, causal=True)

    spec = P(None, "sp", "tp", None)
    fn = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_pipeline_matches_sequential():
    n_stage, m, mb, dim = 4, 8, 2, 16
    mesh = build_mesh(8, pp=4, tp=2, sp=1, dp=1)
    key = jax.random.PRNGKey(3)
    ws = jax.random.normal(key, (n_stage, dim, dim)) / np.sqrt(dim)
    x = jax.random.normal(jax.random.PRNGKey(4), (m, mb, dim))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    # sequential oracle
    want = x
    for s in range(n_stage):
        want = stage_fn(ws[s], want)

    def inner(w_local, x_mb):
        return pipeline.pipeline_spmd(stage_fn, w_local[0], x_mb, axis_name="pp")

    fn = jax.shard_map(
        inner, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(), check_vma=False,
    )
    got = jax.jit(fn)(ws, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_ring_attention_degenerate_ring_uses_flash(monkeypatch):
    """sp axis of size 1 must route to the standalone flash kernel
    (kernel backward + remat policy) and still match the oracle."""
    import numpy as np

    from dmlc_tpu.parallel import build_mesh
    from dmlc_tpu.parallel.ring_attention import (
        make_sharded_ring_attention, ring_attention_reference)

    import dmlc_tpu.ops.flash_attention as _flash

    mesh = build_mesh(1, dp=1, sp=1, tp=1, pp=1, ep=1)
    b, t, h, d = 1, 64, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = [jax.random.normal(k_, (b, t, h, d), jnp.float32) for k_ in ks]
    want = ring_attention_reference(q, k, v, causal=True)
    calls = []
    orig = _flash.flash_attention
    monkeypatch.setattr(
        _flash, "flash_attention",
        lambda *a, **kw: calls.append(1) or orig(*a, **kw))
    got = make_sharded_ring_attention(mesh, causal=True, impl="flash")(q, k, v)
    assert calls, "n==1 ring must route to the standalone flash kernel"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and gradients flow through the standalone custom_vjp path
    g = jax.grad(lambda q_: jnp.sum(make_sharded_ring_attention(
        mesh, causal=True, impl="flash")(q_, k, v)))(q)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_flash_impl_matches_reference(causal):
    # the Pallas kernel (interpret mode on CPU) wired into the ring loop
    mesh = build_mesh(8, sp=4, tp=2, pp=1, dp=1)
    b, t, h, d = 1, 64, 2, 128  # d aligned for the kernel's lane gate
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, d), jnp.float32)

    want = ring_attention_reference(q, k, v, causal=causal)
    fn = make_sharded_ring_attention(mesh, causal=causal, impl="flash")
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("sp,tp,t,causal", [
    (2, 1, 64, True),    # t_local 32: two backward blocks of 16
    (2, 2, 48, False),   # t_local 24: a padded block; heads split over tp
    (4, 2, 96, True),    # the same, causal, four ring steps
    (4, 1, 96, False),
])
def test_ring_attention_flash_grads_match_reference(monkeypatch, sp, tp, t,
                                                    causal):
    """The kernel path's own VJP: dQ, dK, dV from the backward kernels at
    global offsets, the dK / dV accumulators travelling the ring, against
    jax.grad of the unsharded oracle."""
    from dmlc_tpu import telemetry

    monkeypatch.setenv("DMLC_FLASH_BWD_BLOCK_Q", "16")
    monkeypatch.setenv("DMLC_FLASH_BWD_BLOCK_K", "16")
    mesh = build_mesh(sp * tp, sp=sp, tp=tp, pp=1, dp=1)
    b, h, d = 1, 2 * tp, 128
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, w = (jax.random.normal(k_, (b, t, h, d), jnp.float32)
                  for k_ in ks)
    fn = make_sharded_ring_attention(mesh, causal=causal, impl="flash")

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) * w)

    def loss_ref(q, k, v):
        return jnp.sum(ring_attention_reference(q, k, v, causal=causal) * w)

    before = telemetry.counters_snapshot().get("flash", {})
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    after = telemetry.counters_snapshot().get("flash", {})
    assert (after.get("ring_step_bwd_calls", 0)
            > before.get("ring_step_bwd_calls", 0))
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


@pytest.mark.parametrize("policy,fwd_calls", [("save_flash", 1),
                                              ("full", 2)])
def test_ring_flash_forward_runs_once_under_save_flash(policy, fwd_calls):
    """The kernel path names its (o, lse) as the one-chip call does, so
    under the save_flash remat policy the gradient holds the ring's
    ``flash_fwd`` once (the backward does not run the ring again);
    under "full" it holds it twice."""
    from dmlc_tpu.parallel.ring_attention import ring_attention

    mesh = build_mesh(2, sp=2, tp=1, pp=1, dp=1)
    spec = P(None, "sp", None, None)
    x = jax.ShapeDtypeStruct((1, 64, 2, 128), jnp.float32)
    names = jax.checkpoint_policies.save_only_these_names(
        "flash_o", "flash_lse") if policy == "save_flash" else None

    def local(q, k, v):
        f = jax.checkpoint(lambda q, k, v: jnp.sum(ring_attention(
            q, k, v, axis_name="sp", impl="flash")), policy=names)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    text = str(jax.make_jaxpr(_smap(mesh, local, (spec,) * 3, (spec,) * 3))(
        x, x, x))
    assert text.count("name=flash_fwd") == fwd_calls
    assert text.count("name=flash_dkv") == text.count("name=flash_dq") == 2


# ---------------------------------------------------------------------------
# bucketed_psum_mean (parallel/overlap.py device path): one lax.psum per
# reverse-topological bucket must equal the fused pmean — vmap's named
# axis exercises the psum semantics without needing shard_map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket_bytes_", [16, 64, 1 << 20])
def test_bucketed_psum_mean_matches_fused(bucket_bytes_):
    from dmlc_tpu.parallel.overlap import bucketed_psum_mean

    n = 4
    rng = np.random.default_rng(0)
    tree = {
        "w": rng.normal(size=(n, 3, 5)).astype(np.float32),
        "b": rng.normal(size=(n, 7)).astype(np.float32),
        "scale": rng.normal(size=(n, 1)).astype(np.float32),
    }

    out = jax.vmap(lambda t: bucketed_psum_mean(
        t, "i", bucket_bytes_=bucket_bytes_), axis_name="i")(tree)
    for key in tree:
        want = np.broadcast_to(tree[key].mean(axis=0, keepdims=True),
                               tree[key].shape)
        np.testing.assert_allclose(np.asarray(out[key]), want,
                                   rtol=1e-6, atol=1e-6)


def test_bucketed_psum_mean_splits_on_dtype_boundary():
    """Mixed-dtype leaves cannot share a concatenated bucket — the
    bucketer must split them, and both dtypes still reduce correctly."""
    from dmlc_tpu.parallel.overlap import bucketed_psum_mean

    n = 2
    tree = [jnp.arange(2 * n, dtype=jnp.float32).reshape(n, 2),
            jnp.arange(3 * n, dtype=jnp.bfloat16).reshape(n, 3)]
    out = jax.vmap(lambda t: bucketed_psum_mean(t, "i", bucket_bytes_=1 << 20),
                   axis_name="i")(tree)
    for got, src in zip(out, tree):
        assert got.dtype == src.dtype
        want = np.broadcast_to(
            np.asarray(src, np.float32).mean(axis=0, keepdims=True),
            src.shape)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=1e-2)


def test_make_train_step_overlap_arg_validation():
    from dmlc_tpu.models import TransformerConfig, make_train_step
    from dmlc_tpu.parallel import build_mesh

    mesh = build_mesh(1, dp=1, sp=1, tp=1, pp=1, ep=1)
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, head_dim=8,
                            d_ff=32, n_layers=1, n_experts=1)
    with pytest.raises(ValueError, match="overlap"):
        make_train_step(mesh, cfg, overlap="bogus")
