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

    k_pool / v_pool : [n_pages, block_size, H_kv, D]
    block_tables    : [B, W] int32   (row b's physical page ids;
                                      rows padded with 0, or a layer's
                                      page 0 — masked off)
    lengths         : [B]    int32   (committed tokens before the window)
    q               : [B, S, H, D]   (post-rope window queries)

A page holds the K/V heads, of which grouped-query attention has fewer
than query heads (``H_kv`` divides ``H``): query head ``h`` reads K/V
head ``h // (H / H_kv)``.  A sliding-window layer (``span`` keys: the
token itself and the ``span - 1`` before it) is called with its own
pool and the sequences' *ring* tables, ``W = ceil(span / block_size) +
1`` entries whatever the context, logical page ``p`` at entry ``p mod
W`` (serving/kv_cache.py); the mask then also drops positions ``<=
lengths[b] + s - span`` and a row's walk starts at the page of the
oldest key it can see, so a sliding layer costs the window, not the
context.

Window position ``s`` of row ``b`` attends pool positions
``p <= lengths[b] + s`` within the table's ``W * block_size`` span —
the caller must have scattered the window's own K/V into the pool at
positions ``lengths[b] .. lengths[b]+S-1`` first (scatter-then-attend),
so this is the causal "cache + new token" mask with the new tokens
living at their real paged addresses.  Dead batch rows (length 0,
table all padding) produce what the engine never samples: the twin
reads the padding page for them, the kernel reads nothing and writes
zeros.

Two implementations: a Pallas TPU kernel that walks each row's pages
itself, and a ``lax``-composed reference (gather inside jit) that runs
everywhere and is the parity oracle; ops/dispatch.py decides which a
call site gets.  interpret=True runs the kernel on CPU for tests.
DMAs and double buffering per /opt/skills/guides/pallas_guide.md and
boom_attention_tricks.md sections 9-11.

Kernel layout.  ONE invocation serves the whole batch: the pools stay
in HBM, the tables and lengths sit in SMEM, q and the output (a few
hundred KB) whole in VMEM.  A row's context is walked in *blocks* of
up to ``_KV_VMEM_BYTES / 4`` of K and as much of V (32 pages = 512
tokens at the flagship's 64 KB page), each page copied into its slot
of a ``[2, pages, bs*H, D]`` VMEM scratch by a DMA of its own, all of
a block's copies in flight at once and the NEXT block's (the next live
row's first, at a row's end) started before the current one is
computed.  How far a row walks follows ``lengths[b]``: it fetches
``ceil((lengths[b] + S) / bs)`` pages and no padded table entry, a
dead row (length 0) fetches nothing and costs one turn of a scalar
loop, and no row pays for another's length.  A block is computed in
*chunks* of a few pages (``_CHUNK_SCORES``), each waited for by its own
semaphore, so the first product starts when the first chunk has
landed.  The TPU tiles a page ``[bs, H_kv, D]`` with the heads on
sublanes, so one head's keys are no slice of it; a chunk is therefore
ONE 2-D product of the ``S*H`` query rows (row ``s*H + h``, unpadded:
16 rows in the flagship's plain decode, 128 under Command A+'s 128
query heads) against the chunk's ``pages*bs*H_kv`` key rows (row
``t*H_kv + h'``), masked to ``h // (H / H_kv) == h'`` and position
``<= lengths[b] + s`` (and inside the window, where there is one) by
one int32 table of ``t - s`` built from iotas once a call, followed by
the online-softmax update in float32 and the value product with ``p``
in the pool's dtype (as the twin does).  The cross-head products cost
the MXU ``H_kv`` times the useful work (sixteen at the flagship's 16
heads, eight at Command A+'s 8 K/V heads, where the 16 query heads of
a group fill the rows that 16 separate heads left masked) and it does
not show: a key row is loaded into the MXU once either way.  Measured
on the v5e against a per-head form over head-major pages ``[H, bs,
D]``, this one is the faster (PERF.md, PR 30), so the pool keeps its
layout, under grouped heads too (PERF.md, PR 33).  Block and chunk sizes follow from
the shapes (:func:`_walk_shape`), not from a knob.

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


def supports(head_dim: int, block_size: int, n_kv_heads: int) -> bool:
    """Whether the Pallas kernel serves these shapes: the head dim must
    fill whole 128-element lanes, and so must one page's ``bs*H_kv``
    key rows (a chunk of them is the score matrix's lane dim)."""
    return head_dim % 128 == 0 and (block_size * n_kv_heads) % 128 == 0


def _first_key(lengths, span: int):
    """The oldest position a row's first window token (at
    ``lengths[b]``) sees under a sliding window of ``span`` keys."""
    return jnp.maximum(lengths - (span - 1), 0)


def _lax_paged_attention(q, k_pool, v_pool, block_tables, lengths, scale,
                         span: int = 0):
    """Gather-composed fallback: the block gather happens INSIDE jit
    (one fused gather per layer, no host staging, no dense [B, maxlen]
    intermediate on the host) and the math is dense softmax attention
    with an f32 score path — the 1e-5 parity contract the kernel is
    held to.  Under ``span`` the table is a ring: entry ``i`` holds the
    newest logical block ``j = i (mod W)`` the row has reached."""
    b, s_w, h, d = q.shape
    w = block_tables.shape[1]
    bs, h_kv = k_pool.shape[1], k_pool.shape[2]
    k_ctx = k_pool[block_tables].reshape(b, w * bs, h_kv, d)
    v_ctx = v_pool[block_tables].reshape(b, w * bs, h_kv, d)
    qg = q.reshape(b, s_w, h_kv, h // h_kv, d)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k_ctx,
                   preferred_element_type=jnp.float32) * scale
    limit = lengths[:, None] + jnp.arange(s_w)[None, :]          # [B, S]
    entry = jnp.arange(w)[None, :]
    if span:
        newest = (lengths[:, None] + s_w - 1) // bs              # [B, 1]
        block = newest - (newest - entry) % w                    # [B, W]
    else:
        block = jnp.broadcast_to(entry, (b, w))
    pos = (block[:, :, None] * bs + jnp.arange(bs)).reshape(b, 1, w * bs)
    keep = (pos >= 0) & (pos <= limit[:, :, None])               # [B, S, K]
    if span:
        keep = keep & (pos > limit[:, :, None] - span)
    s = jnp.where(keep[:, None, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v_ctx.dtype), v_ctx,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s_w, h, d).astype(q.dtype)


#: VMEM the walk's K and V blocks take together: two of each, so that
#: one block's pages land while the other is computed
_KV_VMEM_BYTES = 8 << 20
#: float32 scores one compute chunk makes, ``[S*H, chunk keys]``: the
#: softmax of a chunk stays in registers' reach
_CHUNK_SCORES = 32 * 1024
#: scoped VMEM asked of the compiler: the blocks, the chunk's
#: temporaries, the mask table, q and the output with room to spare
_VMEM_LIMIT_BYTES = 32 << 20


def _walk_shape(rows: int, cols: int, d: int, itemsize: int,
                w: int) -> tuple:
    """``(pages a compute chunk, chunks a block)`` from the shapes: a
    chunk's scores fit ``_CHUNK_SCORES``, two K and two V blocks fit
    ``_KV_VMEM_BYTES``, and neither outgrows a table row."""
    chunk = max(1, min(_CHUNK_SCORES // (rows * cols), w))
    fit = _KV_VMEM_BYTES // (4 * chunk * cols * d * itemsize)
    return chunk, max(1, min(fit, -(-w // chunk)))


def _kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, diff_ref, *, n_heads: int, n_kv_heads: int,
            bs: int, s_w: int, chunk: int, scale: float, span: int):
    """One invocation walks every row: ``k_buf`` / ``v_buf``
    ``[2, pages, bs*H_kv, D]`` are the two blocks, ``sems[kv, slot, c]``
    counts the copies of chunk ``c`` of a block.  Under ``span`` a
    row's walk starts at the page of the oldest key its window reaches
    and the table is a ring (page ``p`` at entry ``p mod W``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_rows, rows, d = q_ref.shape
    pages = k_buf.shape[1]
    ckeys = chunk * bs * n_kv_heads
    width = tbl_ref.shape[1]
    group = n_heads // n_kv_heads

    # the mask's static part, once a call: key column c = t*H_kv + h'
    # may meet query row r = s*H + h where h reads K/V head h', t - s
    # tokens past the chunk's first position
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, ckeys), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, ckeys), 1)
    diff_ref[...] = jnp.where(r % n_heads // group == c % n_kv_heads,
                              c // n_kv_heads - r // n_heads, 1 << 30)

    # a row's last block copies its live pages only; what the slot
    # holds behind them is multiplied by p = 0, so it must be finite:
    # stale pages are, the scratch's first bytes need not be
    def _zero(i, _):
        v_buf[i // pages, i % pages] = jnp.zeros(v_buf.shape[2:],
                                                 v_buf.dtype)
        return _
    jax.lax.fori_loop(0, 2 * pages, _zero, 0)

    def first_page(b):  # of row b's walk
        return _first_key(len_ref[b], span) // bs if span else 0

    def n_pages(b):  # pages row b walks; a dead row walks none
        return jnp.where(len_ref[b] > 0,
                         pl.cdiv(len_ref[b] + s_w, bs) - first_page(b), 0)

    def live_pages(b, j):  # of block j of row b
        return jnp.clip(n_pages(b) - j * pages, 0, pages)

    def copies(b, j, slot, i):  # page i of block j of row b
        at = first_page(b) + j * pages + i
        page = tbl_ref[b, at % width if span else at]
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, i],
                                      sems.at[0, slot, i // chunk]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, i],
                                      sems.at[1, slot, i // chunk]))

    def start(b, j, slot):
        def one(i, _):
            for copy in copies(b, j, slot, i):
                copy.start()
            return _
        jax.lax.fori_loop(0, live_pages(b, j), one, 0)

    def next_live(b):  # the first live row at or after b, or n_rows
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(
                i < n_rows, len_ref[jnp.minimum(i, n_rows - 1)] <= 0),
            lambda i: i + 1, b)

    first = next_live(0)

    @pl.when(first < n_rows)
    def _prologue():
        start(first, 0, 0)

    def row(b, slot):
        n_blocks = pl.cdiv(n_pages(b), pages)
        page0 = first_page(b)
        # whose first block to fetch during this row's last one
        nxt = next_live(jnp.where(n_blocks > 0, b + 1, n_rows))
        q = q_ref[b]                                   # [S*H, D]

        def block(j, carry):
            slot, m, l, acc = carry
            last = j + 1 == n_blocks
            ahead_b = jnp.where(last, nxt, b)

            @pl.when(ahead_b < n_rows)
            def _fetch_ahead():
                start(ahead_b, jnp.where(last, 0, j + 1), 1 - slot)

            live = live_pages(b, j)

            def step(ci, carry):
                m, l, acc = carry

                def landed(i, _):
                    for copy in copies(b, j, slot, i):
                        copy.wait()
                    return _
                jax.lax.fori_loop(ci * chunk,
                                  jnp.minimum(live, (ci + 1) * chunk),
                                  landed, 0)
                at = pl.ds(ci * chunk, chunk)
                k = k_buf[slot, at].reshape(ckeys, d)  # row = t*H + h
                v = v_buf[slot, at].reshape(ckeys, d)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                # the head's own K/V head AND key position <=
                # lengths[b] + s (and inside the window, if there is one)
                ahead = len_ref[b] - (page0 + j * pages + ci * chunk) * bs
                keep = diff_ref[...] <= ahead
                if span:
                    keep = jnp.logical_and(keep,
                                           diff_ref[...] > ahead - span)
                s = jnp.where(keep, s, _NEG_BIG)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                # every row keeps the walk's first position (a window
                # token sees at least itself, and the walk starts in
                # the page of token 0's oldest key), so m_new is a real
                # score from the first chunk on and a masked column's p
                # is 0
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                acc = acc * corr + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l, acc

            m, l, acc = jax.lax.fori_loop(0, pl.cdiv(live, chunk), step,
                                          (m, l, acc))
            return 1 - slot, m, l, acc

        slot, _, l, acc = jax.lax.fori_loop(
            0, n_blocks, block,
            (slot, jnp.full((rows, 1), _NEG_BIG, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, d), jnp.float32)))
        # a dead row walked nothing: acc = l = 0 writes zeros
        o_ref[b] = acc / jnp.maximum(l, 1e-37)
        return slot

    jax.lax.fori_loop(0, n_rows, row, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "span"))
def _walk_pool(q, k_pool, v_pool, block_tables, lengths, scale, interpret,
               span=0):
    """The kernel's call.  Jitted so that a decode program's layers,
    whose calls differ in their tables' values alone, trace and lower
    the walk once and not once a layer; its name keeps clear of the
    kernel's, which the benchmark counts by."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_w, h, d = q.shape
    w = block_tables.shape[1]
    n_pages, bs, h_kv = k_pool.shape[:3]
    rows, cols = s_w * h, bs * h_kv
    chunk, per_block = _walk_shape(rows, cols, d, k_pool.dtype.itemsize, w)
    pages = chunk * per_block
    block = pltpu.VMEM((2, pages, cols, d), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, n_heads=h, n_kv_heads=h_kv, bs=bs,
                          s_w=s_w, chunk=chunk, scale=scale, span=span),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # the tables
                  pl.BlockSpec(memory_space=pltpu.SMEM),   # the lengths
                  pl.BlockSpec(memory_space=pltpu.VMEM),   # q, whole
                  pl.BlockSpec(memory_space=pl.ANY),       # the pools stay
                  pl.BlockSpec(memory_space=pl.ANY)],      # in HBM
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), jnp.float32),
        scratch_shapes=[block, block,
                        pltpu.SemaphoreType.DMA((2, 2, per_block)),
                        pltpu.VMEM((rows, chunk * cols), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="paged_attn",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(b, rows, d),                             # row = s*H + h
      k_pool.reshape(n_pages, cols, d),                  # row = t*H_kv + h'
      v_pool.reshape(n_pages, cols, d))
    # float32 out of the kernel: whatever XLA does to hand the result
    # on (a cast, a layout for the o projection) is then an op of its
    # own name and not a second event under the kernel's
    return out.astype(q.dtype).reshape(b, s_w, h, d)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    scale: Optional[float] = None, impl: str = "auto",
                    interpret: bool = False, span: int = 0):
    """Window attention against a paged KV pool.

    See the module docstring for shapes and the mask contract.  Returns
    ``[B, S, H, D]`` in q's dtype.  ``span`` > 0: a sliding layer, whose
    ``block_tables`` is the ring table.  ``impl``: "auto" takes the Pallas
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

    if span and q.shape[1] > 1 + (-span % int(k_pool.shape[1])):
        # the ring holds ceil(span / bs) + 1 pages: a window of S
        # tokens reaches one more unless the slack covers it
        raise ValueError(f"a decode window of {q.shape[1]} tokens does "
                         f"not fit a sliding layer's ring (span {span})")
    mode = dispatch.choose(
        supports(d, int(k_pool.shape[1]), int(k_pool.shape[2])), impl)
    if mode != dispatch.LAX:
        return _walk_pool(
            q, k_pool, v_pool, block_tables, lengths, scale=float(scale),
            interpret=bool(interpret or mode == dispatch.INTERPRET),
            span=int(span))
    return _lax_paged_attention(q, k_pool, v_pool, block_tables, lengths,
                                float(scale), int(span))


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
