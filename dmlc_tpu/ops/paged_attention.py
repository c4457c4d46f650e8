"""Paged decode attention: attend straight into the block pool.

The decode fast path's kernel: queries for a small per-sequence window
of tokens (one token in plain decode, ``k+1`` in a speculative-verify
step) attend against that sequence's KV blocks *in place*, addressed
through a per-sequence block table — no dense ``[B, maxlen, H, D]``
gather is ever materialized and no re-placement copy runs per
iteration.  A pool here is any run of pages.  The cache keeps its
pools layer-major (``kv_cache.PagedKVCache``), and the decode program
passes ALL layers' pages as one run, the free view ``[L * n_blocks,
...]`` of its donated pool, with layer ``li``'s tables shifted by
``li * n_blocks``: a per-layer slice ``pool[li]`` would be copied for
the kernel at every call.

    k_pool / v_pool : [n_pages, block_size, H, D]
    block_tables    : [B, W] int32   (row b's physical page ids;
                                      rows padded with 0, or a layer's
                                      page 0 — masked off)
    lengths         : [B]    int32   (committed tokens before the window)
    q               : [B, S, H, D]   (post-rope window queries)

Window position ``s`` of row ``b`` attends pool positions
``p <= lengths[b] + s`` within the table's ``W * block_size`` span —
the caller must have scattered the window's own K/V into the pool at
positions ``lengths[b] .. lengths[b]+S-1`` first (scatter-then-attend),
so this is the causal "cache + new token" mask with the new tokens
living at their real paged addresses.  Dead batch rows (length 0,
table all padding) read the padding page and produce garbage the
engine never samples.

Two implementations: a Pallas TPU kernel whose block-table indirection
lives in the BlockSpec index map (the scalar-prefetched table picks
which physical block each grid step DMAs — the PagedAttention trick),
and a ``lax``-composed reference (gather inside jit) that runs
everywhere and is the parity oracle; ops/dispatch.py decides which a
call site gets.  interpret=True runs the kernel on CPU for tests.
Layout/tiling per /opt/skills/guides/pallas_guide.md; the step body
mirrors ops/flash_attention.py's forward at G=1 (KV walk in the grid,
f32 accumulators in the revisited output blocks, predicated skip of
fully-masked blocks).

Kernel layout.  The TPU lowering only takes blocks whose last two dims
are tile multiples or the whole array dims, so a ``[bs, 1, D]`` slice
of one head out of a ``[bs, H, D]`` page is not a legal block.  The
kernel therefore takes WHOLE pages: the pool is viewed (free reshape)
as ``[n_blocks, bs*H, D]`` and one grid step ``(b, j)`` multiplies all
``H*S`` query rows of sequence ``b`` against all ``bs*H`` key rows of
its j-th page in one 2-D matmul.  Query row ``(h, s)`` may only see
key row ``(t, h')`` when ``h == h'`` and the position is in range;
both conditions fold into ONE static int32 table ``pair[r, c] = t - s``
(a huge value where the heads differ), compared against the scalar
``lengths[b] - j*bs``.  The cross-head products are wasted MXU work
(H-fold), which decode has to spare; the grid and its speed are
ROADMAP A4.

Latent attention (MLA) decodes against another cache: ONE row per
token and layer, ``[rms(c_kv) | rope(k_pe)]``, with no head axis and no
separate V.  :func:`latent_paged_attention` is that kernel
(``mla_paged_attn``) and its lax twin: the absorbed queries of all
heads ``[S*H, row]`` meet a page in one 2-D product with no cross-head
waste (every head reads the same keys), and the values are the row's
first ``v_dim`` entries of the page already in VMEM.  A page lies
``[row, bs]``, slots minor: K^T as the score product takes it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_NEG_BIG = -1e30

__all__ = ["paged_attention", "supports", "latent_paged_attention",
           "latent_supports"]


def supports(head_dim: int, block_size: int, n_heads: int) -> bool:
    """Whether the Pallas kernel serves these shapes: the head dim must
    fill whole 128-element lanes, and so must one page's ``bs*H`` key
    rows (they are the score matrix's lane dim); the window is padded
    to whole sublanes inside."""
    return head_dim % 128 == 0 and (block_size * n_heads) % 128 == 0


def _lax_paged_attention(q, k_pool, v_pool, block_tables, lengths, scale):
    """Gather-composed fallback: the block gather happens INSIDE jit
    (one fused gather per layer, no host staging, no dense [B, maxlen]
    intermediate on the host) and the math is dense softmax attention
    with an f32 score path — the 1e-5 parity contract the kernel is
    held to."""
    b, s_w, h, d = q.shape
    w = block_tables.shape[1]
    bs = k_pool.shape[1]
    k_ctx = k_pool[block_tables].reshape(b, w * bs, h, d)
    v_ctx = v_pool[block_tables].reshape(b, w * bs, h, d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_ctx,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(w * bs)
    limit = lengths[:, None] + jnp.arange(s_w)[None, :]          # [B, S]
    keep = pos[None, None, :] <= limit[:, :, None]               # [B, S, K]
    s = jnp.where(keep[:, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_ctx.dtype), v_ctx,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, pair_ref,
            pv_ref, m_ref, l_ref, *, bs: int, s_real: int, scale: float):
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        pv_ref[...] = jnp.zeros_like(pv_ref[...])
        m_ref[...] = jnp.full_like(m_ref[...], _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref[...])

    # the last pool position any window row of this sequence may
    # attend; pages entirely past it are predicated no-op visits
    limit = len_ref[bi] + s_real - 1

    @pl.when(j * bs <= limit)
    def _step():
        q = q_ref[...]                                 # [1, H*S_pad, D]
        kb = k_ref[...]                                # [1, bs*H, D]
        vb = v_ref[...]
        s = jax.lax.dot_general(
            q, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [1, rows, cols]
        # same head AND key position j*bs + t <= lengths[b] + s
        keep = pair_ref[...] <= len_ref[bi] - j * bs
        s = jnp.where(keep, s, _NEG_BIG)
        m_old = m_ref[..., 0]                          # [1, rows]
        l_old = l_ref[..., 0]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=2))
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_old - m_new)
        l_new = l_old * corr + jnp.sum(p, axis=2)
        pv = jax.lax.dot_general(
            p, vb.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [1, rows, D]
        pv_ref[...] = pv_ref[...] * corr[..., None] + pv
        # per-row scalars broadcast over an 8-lane minor axis (Mosaic
        # lane tiling, same storage trick as flash_attention)
        m_ref[...] = jnp.broadcast_to(m_new[..., None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[..., None], l_ref.shape)


@functools.lru_cache(maxsize=8)  # read-only; one per (H, window, page)
def _pair_table(h: int, s_pad: int, bs: int) -> np.ndarray:
    """``[1, H*S_pad, bs*H]`` int32: ``t - s`` where query row
    ``r = h*S_pad + s`` and key row ``c = t*H + h'`` share a head, a
    value no length can reach where they do not."""
    r = np.arange(h * s_pad)
    c = np.arange(bs * h)
    same = (r // s_pad)[:, None] == (c % h)[None, :]
    diff = (c // h)[None, :] - (r % s_pad)[:, None]
    return np.where(same, diff, 1 << 30).astype(np.int32)[None]


def _pallas_paged_attention(q, k_pool, v_pool, block_tables, lengths,
                            scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_w, h, d = q.shape
    w = block_tables.shape[1]
    n_blocks, bs = k_pool.shape[:2]
    s_pad = -(-s_w // 8) * 8  # window rows fill whole sublanes
    rows, cols = h * s_pad, bs * h
    qt = jnp.transpose(q, (0, 2, 1, 3))                  # [B, H, S, D]
    if s_pad != s_w:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, s_pad - s_w), (0, 0)))
    qt = qt.reshape(b, rows, d)
    kf = k_pool.reshape(n_blocks, cols, d)               # row = t*H + h
    vf = v_pool.reshape(n_blocks, cols, d)
    tbl = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    pair = jnp.asarray(_pair_table(h, s_pad, bs))

    # the paged indirection: the K/V index maps read the scalar-
    # prefetched block table to pick which PHYSICAL page each grid
    # step DMAs — the kernel walks row b's logical pages j=0..W-1 but
    # the pool is only ever touched at the table's addresses
    of_seq = lambda bi, j, tbl_, lens_: (bi, 0, 0)  # noqa: E731
    q_spec = pl.BlockSpec((1, rows, d), of_seq)
    kv_spec = pl.BlockSpec((1, cols, d),
                           lambda bi, j, tbl_, lens_: (tbl_[bi, j], 0, 0))
    pair_spec = pl.BlockSpec((1, rows, cols),
                             lambda bi, j, tbl_, lens_: (0, 0, 0))
    acc_spec = pl.BlockSpec((1, rows, d), of_seq)
    ml_spec = pl.BlockSpec((1, rows, 8), of_seq)
    pv, _, l = pl.pallas_call(
        functools.partial(_kernel, bs=bs, s_real=s_w, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, w),  # innermost page walk revisits sequence bi
            in_specs=[q_spec, kv_spec, kv_spec, pair_spec],
            out_specs=[acc_spec, ml_spec, ml_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 8), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 8), jnp.float32),
        ],
        name="paged_attn",
        interpret=interpret,
    )(tbl, lens, qt, kf, vf, pair)
    out = pv / jnp.maximum(l[..., :1], 1e-37)            # [B, rows, D]
    out = out.reshape(b, h, s_pad, d)[:, :, :s_w]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    scale: Optional[float] = None, impl: str = "auto",
                    interpret: bool = False):
    """Window attention against a paged KV pool.

    See the module docstring for shapes and the mask contract.  Returns
    ``[B, S, H, D]`` in q's dtype.  ``impl``: "auto" takes the Pallas
    kernel or the lax reference as ops/dispatch decides (the kernel on
    a TPU when :func:`supports` allows); "pallas"/"lax" force a path
    (tests drive the kernel on CPU with ``impl="pallas"``, which runs
    it under the interpreter there, as ``interpret=True`` always does).
    """
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / d ** 0.5
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    from . import dispatch

    mode = dispatch.choose(
        supports(d, int(k_pool.shape[1]), int(q.shape[2])), impl)
    if mode != dispatch.LAX:
        return _pallas_paged_attention(
            q, k_pool, v_pool, block_tables, lengths, float(scale),
            interpret or mode == dispatch.INTERPRET)
    return _lax_paged_attention(q, k_pool, v_pool, block_tables, lengths,
                                float(scale))


# ---- latent attention: one row per token, shared by every head --------

def latent_supports(row_dim: int, v_dim: int, block_size: int) -> bool:
    """Whether the latent kernel serves these shapes: the values fill
    whole 128-element lanes and the RoPE'd tail starts on one (so both
    slices of a page are lane-aligned), and a page's rows, the score
    matrix's lane dim, fill whole lanes too."""
    return (v_dim % 128 == 0 and v_dim < row_dim
            and block_size % 128 == 0)


def _lax_latent_paged_attention(q, pool, block_tables, lengths, v_dim,
                                scale):
    """Gather-composed twin of the latent kernel: same mask, f32 score
    path."""
    b, s_w, h, r = q.shape
    w = block_tables.shape[1]
    bs = pool.shape[2]
    ctx = jnp.swapaxes(pool[block_tables], 2, 3).reshape(b, w * bs, r)
    s = jnp.einsum("bqhr,bkr->bhqk", q, ctx,
                   preferred_element_type=jnp.float32) * scale
    limit = lengths[:, None] + jnp.arange(s_w)[None, :]          # [B, S]
    keep = jnp.arange(w * bs)[None, None, :] <= limit[:, :, None]
    s = jnp.where(keep[:, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkr->bqhr", p.astype(ctx.dtype),
                     ctx[..., :v_dim], preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _latent_kernel(tbl_ref, len_ref, q_ref, kv_ref, pair_ref,
                   pv_ref, m_ref, l_ref, *, bs: int, s_real: int,
                   v_dim: int, scale: float):
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        pv_ref[...] = jnp.zeros_like(pv_ref[...])
        m_ref[...] = jnp.full_like(m_ref[...], _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref[...])

    limit = len_ref[bi] + s_real - 1

    @pl.when(j * bs <= limit)
    def _step():
        q = q_ref[...]                                 # [1, S*H, row]
        page = kv_ref[...]                             # [1, row, bs]
        # the score in its two lane-aligned parts: latent . latent and
        # rope . rope (q's row is 4.5 lane tiles wide)
        dims = (((2,), (1,)), ((0,), (0,)))
        s = (jax.lax.dot_general(q[..., :v_dim], page[:, :v_dim], dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q[..., v_dim:], page[:, v_dim:], dims,
                                   preferred_element_type=jnp.float32)
             ) * scale                                 # [1, S*H, bs]
        # key position j*bs + t <= lengths[b] + s
        keep = pair_ref[...] <= len_ref[bi] - j * bs
        s = jnp.where(keep, s, _NEG_BIG)
        m_old = m_ref[..., 0]
        l_old = l_ref[..., 0]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=2))
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_old - m_new)
        l_new = l_old * corr + jnp.sum(p, axis=2)
        pv = jax.lax.dot_general(
            p.astype(page.dtype), page[:, :v_dim],
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [1, S*H, v_dim]
        pv_ref[...] = pv_ref[...] * corr[..., None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[..., None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[..., None], l_ref.shape)


@functools.lru_cache(maxsize=8)
def _latent_pair_table(rows: int, h: int, bs: int) -> np.ndarray:
    """``[1, rows, bs]`` int32: ``t - s`` for query row ``r = s*H + h``
    and key row ``t`` of a page; padding rows (past S*H) see nothing."""
    r = np.arange(rows)
    diff = np.arange(bs)[None, :] - (r // h)[:, None]
    return diff.astype(np.int32)[None]


def _pallas_latent_paged_attention(q, pool, block_tables, lengths, v_dim,
                                   scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_w, h, r = q.shape
    w = block_tables.shape[1]
    bs = pool.shape[2]
    rows = -(-s_w * h // 16) * 16  # whole sublane tiles, bf16's 16 too
    qf = q.reshape(b, s_w * h, r)                        # row = s*H + h
    if rows != s_w * h:
        qf = jnp.pad(qf, ((0, 0), (0, rows - s_w * h), (0, 0)))
    pair = jnp.asarray(_latent_pair_table(rows, h, bs))
    of_seq = lambda bi, j, tbl_, lens_: (bi, 0, 0)  # noqa: E731
    pv, _, l = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, s_real=s_w, v_dim=v_dim,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, w),
            in_specs=[
                pl.BlockSpec((1, rows, r), of_seq),
                # the paged indirection, as in paged_attn
                pl.BlockSpec((1, r, bs),
                             lambda bi, j, tbl_, lens_: (tbl_[bi, j], 0, 0)),
                pl.BlockSpec((1, rows, bs),
                             lambda bi, j, tbl_, lens_: (0, 0, 0)),
            ],
            out_specs=[pl.BlockSpec((1, rows, v_dim), of_seq),
                       pl.BlockSpec((1, rows, 8), of_seq),
                       pl.BlockSpec((1, rows, 8), of_seq)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, rows, v_dim), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 8), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 8), jnp.float32),
        ],
        name="mla_paged_attn",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qf, pool,
      pair)
    out = pv / jnp.maximum(l[..., :1], 1e-37)
    return out[:, :s_w * h].reshape(b, s_w, h, v_dim).astype(q.dtype)


def latent_paged_attention(q, pool, block_tables, lengths, *, v_dim: int,
                           scale: float, impl: str = "auto",
                           interpret: bool = False):
    """Window attention of absorbed latent queries against one layer's
    paged latent pool.

    q [B, S, H, row] (``[q_nope W_kvb,k^T | q_pe]``); pool [n_blocks,
    row, block_size], a row being ``[rms(c_kv) | rope(k_pe)]``;
    block_tables / lengths and the mask as :func:`paged_attention`
    (scatter-then-attend).  The keys are the rows, the values their
    first ``v_dim`` entries.  Returns ``o' [B, S, H, v_dim]`` in q's
    dtype, to be up-projected by the caller.  ``impl`` as
    :func:`paged_attention`."""
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    from . import dispatch

    mode = dispatch.choose(
        latent_supports(int(q.shape[-1]), v_dim, int(pool.shape[2])), impl)
    if mode != dispatch.LAX:
        return _pallas_latent_paged_attention(
            q, pool, block_tables, lengths, v_dim, float(scale),
            interpret or mode == dispatch.INTERPRET)
    return _lax_latent_paged_attention(q, pool, block_tables, lengths,
                                       v_dim, float(scale))
