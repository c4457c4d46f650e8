"""Pallas flash-attention kernel vs the exact oracle (interpret mode on
the CPU mesh; the same kernel compiles for TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_tpu.ops.flash_attention import (
    block_attend_flash,
    flash_attention,
    lax_attention,
    supports,
)
from dmlc_tpu.parallel.ring_attention import (
    _block_attend,
    ring_attention_reference,
)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(causal):
    b, t, h, d = 2, 64, 2, 128
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, d), jnp.float32)
    want = ring_attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("qoff,kvoff,tk", [
    (32, 32, 32),    # the ring's diagonal step: triangular
    (64, 32, 32),    # a shard behind the queries: every key visible
    (40, 32, 32),    # offsets apart by less than a block
    (0, 16, 32),     # rows 0-15 see no key at all: m stays -1e30, l 0
    (8, 16, 32),     # rows 8-15 see none INSIDE a block that is computed:
                     # a start at the mask value would count its masked
                     # scores as exp(0) each
    (32, 32, 40),    # a padded K/V tail under the causal mask
], ids=["diagonal", "behind", "skewed", "unseen_rows", "unseen_in_block",
        "padded"])
def test_block_attend_matches_lax_with_offsets(qoff, kvoff, tk):
    """The ring-step contract: partial (pv, m, l) with global offsets,
    m in natural-log units and -1e30 where a row saw nothing, whatever
    base and start the kernel's running maximum has inside."""
    b, tq, h, d = 1, 32, 2, 128
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, tq, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, tk, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, tk, h, d), jnp.float32)
    scale = 1.0 / (d ** 0.5)

    gq = qoff + np.arange(tq)[:, None]
    gk = kvoff + np.arange(tk)[None, :]
    mask = jnp.asarray(gq >= gk)
    if (qoff, kvoff) != (64, 32):
        assert bool(mask.all()) is False  # partially masked
    pv_l, m_l, l_l = _block_attend(q, k, v, scale=scale, mask=mask)
    pv_f, m_f, l_f = block_attend_flash(
        q, k, v, scale=scale, causal=True, q_offset=qoff, kv_offset=kvoff,
        block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(pv_f), np.asarray(pv_l), atol=2e-5)
    np.testing.assert_allclose(np.asarray(m_f), np.asarray(m_l), atol=2e-5)
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_l), atol=2e-5)
    unseen = ~np.asarray(mask).any(axis=1)
    assert (np.asarray(m_f)[:, :, unseen] == -1e30).all()
    assert (np.asarray(l_f)[:, :, unseen] == 0).all()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [200, 77])
def test_flash_attention_unaligned_tail(causal, t):
    """T not a multiple of block sizes must pad-and-mask, not silently
    drop tail blocks (rows past the last full block were uncomputed)."""
    b, h, d = 1, 2, 128
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, d), jnp.float32)
    want = ring_attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_block_attend_unaligned_kv_shard():
    """Ring-step shape: KV shard length not a block multiple; the (pv,m,l)
    partials must exclude the padded KV rows."""
    b, tq, tk, h, d = 1, 32, 40, 1, 128
    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, tq, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, tk, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, tk, h, d), jnp.float32)
    scale = 1.0 / (d ** 0.5)
    mask = jnp.ones((tq, tk), bool)
    pv_l, m_l, l_l = _block_attend(q, k, v, scale=scale, mask=mask)
    pv_f, m_f, l_f = block_attend_flash(
        q, k, v, scale=scale, causal=False, q_offset=0, kv_offset=0,
        block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(pv_f), np.asarray(pv_l), atol=2e-5)
    np.testing.assert_allclose(np.asarray(m_f), np.asarray(m_l), atol=2e-5)
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_l), atol=2e-5)


@pytest.mark.parametrize("t", [64, 200])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gradients_match_oracle(causal, t):
    """custom_vjp: d/dq,k,v of the flash path must equal the dense oracle
    (pallas_call itself has no autodiff rule)."""
    b, h, d = 1, 2, 128
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(6), (b, t, h, d), jnp.float32)

    def f_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                            interpret=True)
        return jnp.sum(o * w)

    def f_ref(q, k, v):
        return jnp.sum(ring_attention_reference(q, k, v, causal=causal) * w)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-4, rtol=1e-3)


@pytest.mark.parametrize("qoff,kvoff", [(64, 32), (32, 32), (0, 16)],
                         ids=["behind", "diagonal", "unseen_rows"])
def test_block_attend_flash_gradients_with_offsets(qoff, kvoff):
    """Ring-step VJP: grads through (pv, m, l) with nonzero global offsets
    must match differentiating the lax oracle directly (kernel fwd + lax
    twin bwd must stay in sync)."""
    b, tq, tk, h, d = 1, 32, 32, 2, 128
    key = jax.random.PRNGKey(8)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, tq, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, tk, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, tk, h, d), jnp.float32)
    scale = 1.0 / (d ** 0.5)

    def scalar_of(pv, m, l):
        # touch all three outputs so every cotangent path is exercised
        return (jnp.sum(pv * pv) + jnp.sum(jnp.exp(m - 2.0))
                + jnp.sum(l * l) * 0.1)

    def f_flash(q, k, v):
        pv, m, l = block_attend_flash(
            q, k, v, scale=scale, causal=True, q_offset=qoff,
            kv_offset=kvoff, block_q=16, block_k=16, interpret=True)
        return scalar_of(pv, m, l)

    def f_lax(q, k, v):
        gq = qoff + np.arange(tq)
        gk = kvoff + np.arange(tk)
        mask = jnp.asarray(gq[:, None] >= gk[None, :])
        pv, m, l = _block_attend(q, k, v, scale=scale, mask=mask)
        return scalar_of(pv, m, l)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_lax, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-4, rtol=1e-3)


def test_supports_gate():
    assert supports((1, 64, 2, 128), (1, 64, 2, 128))
    assert not supports((1, 64, 2, 96), (1, 64, 2, 96))  # lane
    # unaligned seq lengths are padded-and-masked in-kernel, so supported
    assert supports((1, 200, 2, 128), (1, 200, 2, 128))
    assert not supports((1, 4, 2, 128), (1, 4, 2, 128))  # tiny


def test_flash_under_jit_with_traced_offsets():
    b, t, h, d = 1, 32, 1, 128
    q = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, d))

    @jax.jit
    def run(q, off):
        pv, m, l = block_attend_flash(
            q, q, q, scale=0.1, causal=True, q_offset=off, kv_offset=0,
            block_q=16, block_k=16, interpret=True)
        return pv

    # q_offset=0 vs kv at 0 is triangular; q_offset=320 is fully visible —
    # the same compiled kernel must produce different results (proving the
    # offsets are traced, not baked in at trace time)
    a = run(q, jnp.int32(0))
    b2 = run(q, jnp.int32(320))
    assert not np.allclose(np.asarray(a), np.asarray(b2))
    # and each run matches the lax oracle for its mask
    q_pos = np.arange(t)
    for off, out in ((0, a), (320, b2)):
        mask = jnp.asarray(off + q_pos[:, None] >= q_pos[None, :])
        pv_l, _, _ = _block_attend(q, q, q, scale=0.1, mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(pv_l),
                                   atol=2e-5)


def _bf16_case(seed, tq, tk, h, h_kv, d=128, dv=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, tq, h, d)).astype(jnp.bfloat16),
            jax.random.normal(ks[1], (1, tk, h_kv, d)).astype(jnp.bfloat16),
            jax.random.normal(ks[2], (1, tk, h_kv, dv)).astype(jnp.bfloat16))


@pytest.mark.parametrize("tq,tk,h,h_kv,d,span,off", [
    (64, 64, 4, 2, 128, 0, None),     # grouped heads
    (32, 96, 16, 1, 128, 20, 64),     # a window, an offset, group 16
    (64, 64, 2, 2, 192, 0, None),     # latent attention's qk 192 / v 128
], ids=["gqa", "window", "mla"])
def test_flash_fwd_o_on_bf16_inputs_against_lax(tq, tk, h, h_kv, d, span,
                                                off):
    """The forward-only kernel on the dtype the models hand it.  Kernel
    and twin both keep float32 scores and probabilities over bf16
    operands and round o to bf16 once, so they may differ by the order
    of float32 sums (1e-6) and, where that crosses a rounding boundary,
    by one bf16 ulp of o: 2^-8 of |o| <= max|v|."""
    q, k, v = _bf16_case(11, tq, tk, h, h_kv, d)
    want = lax_attention(q, k, v, scale=d ** -0.5, span=span, q_offset=off)
    got = flash_attention(q, k, v, span=span, q_offset=off, block_q=16,
                          block_k=16, interpret=True)
    assert got.dtype == jnp.bfloat16
    tol = 2.0 ** -8 * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert err.max() <= tol, (err.max(), tol)
    # the boundary is crossed rarely: most outputs are the same bf16
    assert (err == 0).mean() > 0.9, (err == 0).mean()


def test_flash_kernels_keep_float32_p_whatever_v_dtype():
    """Neither kernel hands the value product bf16 probabilities (PR 37
    timed that: slower on the v5e, and a numerical change besides): the
    ring step's partials and the forward-only output are bit for bit
    the same whether v comes as bf16 or as the same values in float32
    (p following v's dtype would move both by 2^-9 of themselves)."""
    q, k, v = _bf16_case(12, 32, 32, 2, 2)
    kw = dict(scale=128 ** -0.5, causal=True, q_offset=32, kv_offset=32,
              block_q=16, block_k=16, interpret=True)
    got = block_attend_flash(q, k, v, **kw)
    want = block_attend_flash(q, k, v.astype(jnp.float32), **kw)
    for a, b_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    fwd = [flash_attention(q, k, x, q_offset=32, block_q=16, block_k=16,
                           interpret=True) for x in (v, v.astype(jnp.float32))]
    np.testing.assert_array_equal(np.asarray(fwd[0], np.float32),
                                  np.asarray(fwd[1], np.float32))


def test_time_flash_fwd_script_smoke():
    """scripts/time_flash_fwd.py at toy sizes through the interpreter:
    every case builds, runs and reports (its times mean nothing here)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "time_flash_fwd.py")
    spec = importlib.util.spec_from_file_location("time_flash_fwd", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lines = mod.main(["--interpret", "--reps", "1"])
    assert [ln["case"] for ln in lines] == [
        "a", "b", "c", "d1", "d2_diagonal", "d2_behind"]
    assert all(ln["clock"] == "host_clock" and ln["ms"] > 0
               and ln["roofline_share_pct"] is None for ln in lines)
    by = {ln["case"]: ln for ln in lines}
    # toy a: 2 Q blocks of 16 rows at offset 32 against 64 keys
    assert (by["a"]["tiles_unmasked"], by["a"]["tiles_boundary"]) == (20, 8)
    assert by["b"]["boundary_tile_share"] > by["a"]["boundary_tile_share"]
    assert by["d2_behind"]["tiles_boundary"] == 0
    # the tile counter against the issue's figures for Command A+'s
    # sliding layers: a Q block reaches five K/V blocks, two of them
    # boundary; the full layer has one boundary tile a Q block's walk
    assert mod.tiles(8192, 32768, 24576, 0, 1024, 1024, span=4096) == (24, 16)
    assert mod.tiles(8192, 32768, 24576, 0, 1024, 1024) == (220, 8)
