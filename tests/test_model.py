"""Flagship transformer: sharded SPMD loss must match the unsharded
oracle, and the full 5-way-parallel train step must run and learn."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dmlc_tpu.models import (
    TransformerConfig,
    init_params,
    make_train_step,
    param_specs,
    unsharded_loss,
)
from dmlc_tpu.parallel import build_mesh

CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, d_ff=32,
    n_layers=2, n_experts=2, microbatches=2,
)


def _data(key, b=4, t=16, vocab=64):
    ids = jax.random.randint(key, (b, t), 0, vocab)
    labels = jnp.roll(ids, -1, axis=1)
    return ids, labels


@pytest.fixture(scope="module")
def mesh():
    # pp=2, sp=2, tp=2: every interesting axis non-trivial on 8 devices
    return build_mesh(8, pp=2, sp=2, tp=2, dp=1, ep=1)


def test_sharded_loss_matches_oracle(mesh):
    params = init_params(jax.random.PRNGKey(0), CFG, n_stages=2)
    ids, labels = _data(jax.random.PRNGKey(1))
    want = float(unsharded_loss(params, ids, labels, CFG))

    from dmlc_tpu.models.transformer import SHARDED_AXES, forward_local

    specs = param_specs()
    fn = jax.shard_map(
        lambda p, i, l: forward_local(p, i, l, CFG, SHARDED_AXES),
        mesh=mesh, in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=P(),
    )
    got = float(jax.jit(fn)(params, ids, labels))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_train_step_learns(mesh):
    params = init_params(jax.random.PRNGKey(0), CFG, n_stages=2)
    step, init_state = make_train_step(mesh, CFG)
    opt_state = init_state(params)
    ids, labels = _data(jax.random.PRNGKey(2))
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, ids, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


TOPK_CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, d_ff=32,
    n_layers=2, n_experts=4, microbatches=2, moe_topk=2,
    moe_capacity_factor=100.0,  # ample: no drops → exactly equals masked
)


def test_topk_moe_matches_masked_dense_oracle():
    """With ample capacity, top-k routing must equal the dense combine
    with probs zeroed outside the top-k and renormalized."""
    from dmlc_tpu.models.transformer import _moe_topk_ffn
    from dmlc_tpu.ops.core import ShardAxes

    cfg = TOPK_CFG
    params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
    layer_p = jax.tree.map(lambda a: a[0][0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    got = _moe_topk_ffn(x, layer_p, ShardAxes(), cfg)

    # oracle: dense path with a hand-built top-k-masked renormalized gate
    logits = jnp.einsum("bte,ex->btx", x, layer_p["gate"])
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.moe_topk)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    sel = jax.nn.one_hot(topi, cfg.n_experts) * topv[..., None]
    mprobs = jnp.sum(sel, axis=-2)                 # [B,T,X]

    from dmlc_tpu.ops.core import swiglu_ffn

    def one_expert(w_in, w_gate, w_out):
        return swiglu_ffn(x, w_in, w_gate, w_out, ShardAxes(), reduce=False)

    ys = jax.vmap(one_expert)(layer_p["w_in"], layer_p["w_gate"],
                              layer_p["w_out"])
    want = jnp.einsum("xbte,btx->bte", ys, mprobs.astype(ys.dtype))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_topk_moe_sharded_matches_oracle():
    """ep=4-sharded routed MoE (local capacity dispatch) == unsharded."""
    mesh = build_mesh(8, pp=1, sp=1, tp=2, dp=1, ep=4)
    cfg = TOPK_CFG
    params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
    ids, labels = _data(jax.random.PRNGKey(4))
    want = float(unsharded_loss(params, ids, labels, cfg))

    from dmlc_tpu.models.transformer import SHARDED_AXES, forward_local

    specs = param_specs()
    fn = jax.shard_map(
        lambda p, i, l: forward_local(p, i, l, cfg, SHARDED_AXES),
        mesh=mesh, in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=P(),
    )
    got = float(jax.jit(fn)(params, ids, labels))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_topk_moe_overflow_counter():
    """moe_debug_overflow=True must record the dropped-choice fraction
    in the metrics stage 'moe' (silent drops are undiagnosable)."""
    from dmlc_tpu import metrics

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, head_dim=8, d_ff=32,
        n_layers=1, n_experts=4, microbatches=1, moe_topk=2,
        moe_capacity_factor=0.25,  # force overflow
        moe_debug_overflow=True)
    params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
    ids, labels = _data(jax.random.PRNGKey(9), b=4, t=16)
    before = metrics.snapshot().get("moe", {})
    float(unsharded_loss(params, ids, labels, cfg))
    after = metrics.snapshot().get("moe", {})
    checks = after.get("overflow_checks", 0) - before.get(
        "overflow_checks", 0)
    frac = after.get("overflow_fraction_sum", 0.0) - before.get(
        "overflow_fraction_sum", 0.0)
    assert checks >= 1
    assert frac > 0.0  # capacity 0.25 must actually drop choices


def test_topk_moe_train_step_learns():
    mesh = build_mesh(8, pp=1, sp=2, tp=1, dp=2, ep=2)
    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, head_dim=8, d_ff=32,
        n_layers=2, n_experts=4, microbatches=2, moe_topk=2,
        moe_capacity_factor=2.0, remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
    step, init_state = make_train_step(mesh, cfg)
    opt_state = init_state(params)
    ids, labels = _data(jax.random.PRNGKey(5), b=8, t=16)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, ids, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_gradients_match_oracle(mesh):
    """Sharded grads (via VMA transposes) == unsharded autodiff grads."""
    params = init_params(jax.random.PRNGKey(0), CFG, n_stages=2)
    ids, labels = _data(jax.random.PRNGKey(3))

    from dmlc_tpu.models.transformer import SHARDED_AXES, forward_local

    specs = param_specs()
    gfn = jax.shard_map(
        lambda p, i, l: jax.grad(
            lambda q: forward_local(q, i, l, CFG, SHARDED_AXES)
        )(p),
        mesh=mesh, in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=specs,
    )
    got = jax.jit(gfn)(params, ids, labels)
    want = jax.grad(lambda q: unsharded_loss(q, ids, labels, CFG))(params)
    flat_g, _ = jax.tree.flatten(got)
    paths = jax.tree.flatten_with_path(want)[0]
    for (path, w), g in zip(paths, flat_g):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-5, rtol=1e-3,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
        )


def test_remat_policies_are_math_neutral():
    """remat and its policies trade memory for recompute — never math:
    loss and gradients must be bitwise-comparable across full /
    save_flash / save_flash_mlp and remat off."""
    import dataclasses

    ids, labels = _data(jax.random.PRNGKey(5))
    results = []
    for remat, policy in [(False, "save_flash"), (True, "full"),
                          (True, "save_flash"), (True, "save_flash_mlp")]:
        cfg = dataclasses.replace(CFG, remat=remat, remat_policy=policy)
        params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
        loss, grads = jax.value_and_grad(
            lambda p: unsharded_loss(p, ids, labels, cfg))(params)
        gnorm = sum(float(jnp.sum(jnp.abs(g)))
                    for g in jax.tree.leaves(grads))
        results.append((float(loss), gnorm))
    base = results[0]
    for got in results[1:]:
        np.testing.assert_allclose(got[0], base[0], rtol=1e-6)
        np.testing.assert_allclose(got[1], base[1], rtol=1e-5)


def test_unknown_remat_policy_rejected():
    import dataclasses

    cfg = dataclasses.replace(CFG, remat=True, remat_policy="bogus")
    params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
    ids, labels = _data(jax.random.PRNGKey(6))
    with pytest.raises(ValueError, match="remat_policy"):
        unsharded_loss(params, ids, labels, cfg)


def test_prefill_matches_training_forward():
    """Serving prefill is the same math as the training forward: the
    cross entropy of its logits equals unsharded_loss, and right-padding
    must not perturb positions before the true length (causality)."""
    from dmlc_tpu.models import forward_prefill
    from dmlc_tpu.ops.core import ShardAxes, softmax_xent

    params = init_params(jax.random.PRNGKey(0), CFG, n_stages=2)
    ids, labels = _data(jax.random.PRNGKey(3), b=2, t=12)
    want = float(unsharded_loss(params, ids, labels, CFG))
    logits, k, v = forward_prefill(params, ids, CFG)
    got = float(jnp.mean(softmax_xent(logits, labels, ShardAxes())))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert k.shape == (CFG.n_layers, 2, 12, CFG.n_heads, CFG.head_dim)
    # pad two extra columns: everything at t<12 must be unchanged
    ids_pad = jnp.pad(ids, ((0, 0), (0, 2)))
    lp, kp, vp = forward_prefill(params, ids_pad, CFG)
    np.testing.assert_allclose(np.asarray(lp[:, :12]), np.asarray(logits),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kp[:, :, :12]), np.asarray(k),
                               rtol=1e-5, atol=1e-6)
    # the serving engine's last-position head: same logits, no [B,T,V]
    from dmlc_tpu.models import forward_prefill_last

    ll, kl, _ = forward_prefill_last(
        params, ids_pad, jnp.array([11, 11]), CFG)
    np.testing.assert_allclose(np.asarray(ll), np.asarray(logits[:, 11]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kl), np.asarray(kp),
                               rtol=1e-6)


@pytest.mark.parametrize("n0", [4, 6])  # on a block boundary, and off
@pytest.mark.parametrize("window", [1, 3])
def test_decode_step_matches_full_forward(window, n0):
    """The satellite contract: a decode window against the paged pools
    reproduces the full-sequence forward's logits position by position
    with every slot it was not given pre-filled with garbage, and the
    step writes the window's K/V rows into the pools, nothing else."""
    from dmlc_tpu.models import forward_prefill
    from dmlc_tpu.models.transformer import forward_decode_paged

    params = init_params(jax.random.PRNGKey(0), CFG, n_stages=2)
    bs, n_blocks, t_total = 4, 8, n0 + 6
    ids, _ = _data(jax.random.PRNGKey(4), b=2, t=t_total)
    logits_full, k_full, v_full = forward_prefill(params, ids, CFG)
    k_full, v_full = np.asarray(k_full), np.asarray(v_full)

    shape = (CFG.n_layers, n_blocks, bs, CFG.n_heads, CFG.head_dim)
    # garbage sentinel in every slot past the valid region: the length
    # mask must make them invisible, so parity proves masking, not luck
    k_pool = np.full(shape, 7.7, np.float32)
    v_pool = np.full(shape, -7.7, np.float32)
    tables = np.array([[5, 2, 7], [1, 6, 3]], np.int32)  # 12 tokens a row
    rows = np.arange(2)[:, None]

    def at(first, n):
        """``[:, block, slot]`` of both rows' tokens first .. first+n-1."""
        pos = first + np.arange(n)
        return np.s_[:, tables[rows, pos // bs], pos % bs]

    # the prefix, written through the block tables ([L, B, T, H, D])
    k_pool[at(0, n0)] = k_full[:, :, :n0]
    v_pool[at(0, n0)] = v_full[:, :, :n0]
    for pos in range(n0, t_total, window):
        win = slice(pos, pos + window)
        lg, k_new, v_new = forward_decode_paged(
            params, np.asarray(ids[:, win], np.int32),
            np.tile(pos + np.arange(window, dtype=np.int32), (2, 1)),
            jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
            np.full(2, pos, np.int32), CFG)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(logits_full[:, win]),
            rtol=1e-5, atol=1e-5,
            err_msg=f"decode logits diverge in the window at {pos}")
        wrote = np.zeros(shape[:3], bool)
        wrote[at(pos, window)] = True
        for new, old, full in ((np.asarray(k_new), k_pool, k_full),
                               (np.asarray(v_new), v_pool, v_full)):
            np.testing.assert_allclose(new[at(pos, window)],
                                       full[:, :, win], rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(new[~wrote], old[~wrote])
        k_pool, v_pool = np.array(k_new), np.array(v_new)


def test_decode_flops_per_token_is_forward_third():
    from dmlc_tpu.models import decode_flops_per_token, train_flops_per_token

    ctx = 128
    got = decode_flops_per_token(CFG, ctx)
    assert got == pytest.approx(train_flops_per_token(CFG, ctx,
                                                      causal=False) / 3.0)
    # more context strictly costs more attention FLOPs
    assert decode_flops_per_token(CFG, 256) > got
