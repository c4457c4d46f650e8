"""Pallas TPU kernels for flash attention (forward + backward).

This is the MXU hot loop of both the single-chip flagship model and ring
attention (parallel/ring_attention.py).  The forward computes one Q block
against one KV shard with an online softmax, returning the partial
(pv, m, l) triple the ring combiner folds across ranks.  The KV/Q walk
lives in the pallas GRID (see the kernel structure note below), with
f32 accumulators in the revisited output blocks; the global position
offsets are scalar-prefetch arguments so the SAME compiled kernel
serves every ring step (offsets are traced values there).  Causal
steps skip fully-masked KV blocks via a predicated no-op visit,
halving attention compute at large T.

The standalone `flash_attention` entry is fully differentiable with
FlashAttention-style backward kernels (dkv + dq passes over saved
(o, lse) residuals) — no T×T matrix is ever materialized, which is what
makes long-context training fit in HBM.  The ring-step
`block_attend_flash` is differentiable through a pure-lax recompute twin
(its (pv, m, l) outputs feed the ring combine, whose rescales cancel
analytically).

Whether a call site gets these kernels or the pure-lax reference is
decided in ops/dispatch.py; interpret=True runs the kernels on CPU for
tests.  Layout/tiling per /opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import get_env

_NEG_BIG = -1e30
_POS_BIG = 1e30
_MASKED = 2 * _NEG_BIG  # a masked score, under m's start of _NEG_BIG


def _out_struct(shape, dtype, *operands):
    """``out_shape`` entry for a ``pallas_call`` traced inside
    ``jax.shard_map``: with ``check_vma=True`` (the train step's
    default) the output must say which mesh axes it varies over, and it
    varies over every axis any operand does.  Outside shard_map the
    set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# Kernel structure note (performance-critical): the KV/Q walk lives in
# the GRID, not in an in-kernel fori_loop.  A loop whose trip count
# depends on program_id lowers to an unpipelined while loop in Mosaic
# (far slower than the pipelined grid when the kernel was written; not
# timed again on the benchmark's chip), and keeping whole sequences
# resident in VMEM overflows it past T~4k.  With the step in the grid,
# accumulators live in the revisited output blocks (init on the first
# step, finalize implicitly on the last), per-step VMEM is O(block),
# and causally-skipped blocks cost one predicated no-op visit (pl.when)
# instead of compute.
#
# What a tile costs (scripts/time_flash_fwd.py on the v5e, PR 37;
# PERF.md section 6): a 1024 x 1024 tile of 128-wide heads 3.65 us
# unmasked and 3.8 us on a boundary (the matrix unit's least is 2.7), a
# tile at qk 192 / v 128 4.9 and 5.1, a 512 x 512 tile 1.7; a Q
# block's walk 0.6 us on top.  The masks, exp2 in place of exp and a
# bf16 p each moved a tile by under 2%; what halved it was keeping m
# and l in their stored shape (see ``step``).


def _first_kv_block(first_q, kvoff, span: int, block_k: int):
    """The KV block that holds the oldest key the query at global
    position ``first_q`` sees under a sliding window of ``span`` keys
    (itself and the span - 1 before it)."""
    return jnp.maximum(first_q - (span - 1) - kvoff, 0) // block_k


def _kernel(qoff_ref, kvoff_ref, kvend_ref, q_ref, k_ref, v_ref,
            pv_ref, m_ref, l_ref, *, block_q: int, block_k: int,
            causal: bool, kv_padded: bool, scale: float, interpret: bool,
            span: int = 0):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)
    first = j == 0
    if span:
        # under a window the grid's KV axis is only as long as a Q
        # block's reach: step 0 is the block of its oldest visible key
        j = j + _first_kv_block(qoff_ref[0] + qi * block_q, kvoff_ref[0],
                                span, block_k)

    @pl.when(first)
    def _init():
        pv_ref[...] = jnp.zeros_like(pv_ref[...])
        m_ref[...] = jnp.full_like(m_ref[...], _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref[...])

    def step(masked: bool):
        q = q_ref[...]                    # [G, block_q, D]
        kb = k_ref[...]                   # [G, block_k, D]
        vb = v_ref[...]
        g, bq, _ = q.shape
        bk = kb.shape[1]
        # batched over the G fused (b,h) pairs: one grid step moves and
        # computes G attention tiles, amortizing per-step DMA/setup
        s = jax.lax.dot_general(
            q, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [G, bq, bk]
        if masked:
            # a score's q_pos - k_pos is ONE iota difference plus a
            # scalar, held against scalars: the diagonal, the window's
            # edge, the padded tail
            col = lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2)
            rel = lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1) - col
            k0 = kvoff_ref[0] + j * block_k
            ahead = qoff_ref[0] + qi * block_q - k0
            keep = None
            if causal:
                keep = rel >= -ahead
            if span:
                keep = keep & (rel < span - ahead)
            if kv_padded:
                # tail KV rows past the real length are padding
                in_range = col < kvend_ref[0] - k0
                keep = in_range if keep is None else keep & in_range
            # (a masked step is a diagonal, window-edge or padded
            # block, so keep is set.)  _MASKED lies under the running
            # maximum's start, so exp(masked - m_new) is 0 even in a row
            # that has seen no key yet (the first block of a windowed
            # walk has such rows) and p needs no second select
            s = jnp.where(keep, s, _MASKED)
        # m and l stay [G, bq, 8], the shape they are stored in, and the
        # row reductions keep their axis: as [G, bq] vectors each took a
        # relayout between sublanes and lanes and back every step, which
        # cost more than the rest of the step together (PERF.md section
        # 6, PR 37: 6.85 -> 3.65 us a 1024 x 1024 tile of 128-wide heads)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new[..., :1])
        corr = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        # the value product takes p as it is: float32 operands cost the
        # matrix unit nothing here that a timing shows, and casting p to
        # bf16 first made the tile 0.5-2.4% SLOWER (one more pass over
        # the scores).  The lax twin mirrors float32 p so the ring-step
        # VJP recompute stays consistent.
        pv = jax.lax.dot_general(
            p, vb.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        pv_ref[...] = pv_ref[...] * corr[..., :1] + pv
        # (m/l are per-row scalars stored broadcast over an 8-lane minor
        # axis, Mosaic's lane tiling; callers slice lane 0)
        m_ref[...] = m_new

    def walk():
        _dispatch_masked_step(pl, step, qi, j, block_q, block_k, causal,
                              kv_padded, kvend_ref, qoff=qoff_ref[0],
                              kvoff=kvoff_ref[0], span=span)

    if interpret:
        # The Pallas interpreter (jax 0.9.0) evaluates a kernel's
        # top-level equations one by one under the caller's VMA check,
        # where an offset that varies over a shard_map axis (the ring
        # step) cannot meet a constant, but it takes a cond whole: an
        # always-true pl.when lets the CPU tests drive this kernel
        # inside the VMA-checked train step.  Compiled kernels skip it.
        pl.when(j >= 0)(walk)
    else:
        walk()


def _kernel_o(qoff_ref, kvoff_ref, kvend_ref, q_ref, k_ref, v_ref, o_ref,
              pv_ref, m_ref, l_ref, *, n_kv: int, **static):
    """:func:`_kernel` with its accumulators in VMEM scratch and the
    normalised ``o = pv / l`` written once, in the inputs' dtype, at
    the sequence's last KV step: for a forward that needs no (m, l).
    In HBM an 8-lane f32 row is padded to 128 lanes, so m and l of a
    16k-token, 64-head call cost 512 MB each, and pv in f32 twice o."""
    from jax.experimental import pallas as pl

    _kernel(qoff_ref, kvoff_ref, kvend_ref, q_ref, k_ref, v_ref,
            pv_ref, m_ref, l_ref, **static)

    @pl.when(pl.program_id(2) == n_kv - 1)
    def _write():
        o_ref[...] = (pv_ref[...] / jnp.maximum(l_ref[..., :1], 1e-20)
                      ).astype(o_ref.dtype)


def supports(q_shape: Tuple[int, ...], k_shape: Tuple[int, ...],
             v_shape: Optional[Tuple[int, ...]] = None) -> bool:
    """Kernel applicability gate: lane dim multiple of 128, seq dims big
    enough to tile.  Unaligned seq lengths are handled by the kernel's
    pad-and-mask path and block sizes are clamped internally, so neither
    disqualifies.  ``v`` may have a head size of its own (forward only);
    beside whole lanes the one qk size Mosaic was shown to take is
    latent attention's 192 (128 without RoPE + 64 with), the block's
    last dim being the whole array's."""
    _, tq, _, d = q_shape
    tk = k_shape[1]
    dv = d if v_shape is None else v_shape[-1]
    return ((d % 128 == 0 or d == 192) and dv % 128 == 0
            and tq >= 8 and tk >= 8)


def lax_block_attend(q, k, v, *, scale, mask):
    """One Q-block × KV-block partial attention, pure lax — the canonical
    (pv, m, l) contract shared by the ring fallback and the kernel's VJP
    twin.  q: [B,Tq,H,D]; k/v: [B,Tk,H,D]; mask: [Tq,Tk] bool or None."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    m = jnp.max(s, axis=-1)                      # [B, H, Tq]
    p = jnp.exp(s - m[..., None])
    if mask is not None:
        p = p * mask[None, None].astype(p.dtype)
    l = jnp.sum(p, axis=-1)                      # [B, H, Tq]
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    return pv, m, l


def _lax_block_attend(q, k, v, qoff, kvoff, *, scale: float, causal: bool):
    """Offset-based wrapper of lax_block_attend: the recompute target for
    the ring-step VJP (mask built from global positions, as the kernel)."""
    tq, tk = q.shape[1], k.shape[1]
    mask = None
    if causal:
        gq = qoff + jnp.arange(tq)
        gk = kvoff + jnp.arange(tk)
        mask = gq[:, None] >= gk[None, :]
    return lax_block_attend(q, k, v, scale=scale, mask=mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(static, q, k, v, qoff, kvoff):
    return _flash_forward(static, q, k, v, qoff, kvoff)


def _flash_core_fwd(static, q, k, v, qoff, kvoff):
    out = _flash_forward(static, q, k, v, qoff, kvoff)
    return out, (q, k, v, qoff, kvoff)


def _flash_core_bwd(static, res, cts):
    scale, causal = static[0], static[1]
    q, k, v, qoff, kvoff = res
    _, vjp = jax.vjp(
        functools.partial(_lax_block_attend, scale=scale, causal=causal),
        q, k, v, qoff, kvoff)
    dq, dk, dv, _, _ = vjp(cts)
    zero_i = np.zeros(np.shape(qoff), jax.dtypes.float0)
    return dq, dk, dv, zero_i, zero_i


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def block_attend_flash(q, k, v, *, scale: float, causal: bool,
                       q_offset, kv_offset,
                       block_q: int = 512, block_k: int = 512,
                       interpret: bool = False):
    """Partial attention of q against one KV shard (the ring step).

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; q_offset/kv_offset: traced
    int32 global positions of element 0.  Returns (pv [B,Tq,H,D] f32,
    m [B,H,Tq] f32, l [B,H,Tq] f32) — same contract as the lax
    _block_attend in ring_attention.  Differentiable: the forward runs
    the Pallas kernel, the backward rematerializes through the lax twin.
    """
    from .. import telemetry

    telemetry.inc("flash", "ring_step_calls")
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    kvoff = jnp.asarray(kv_offset, jnp.int32).reshape(1)
    static = (float(scale), bool(causal), int(block_q), int(block_k),
              bool(interpret))
    return _flash_core(static, q, k, v, qoff, kvoff)


def _pad_seq(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else x


def _flash_forward(static, q, k, v, qoff, kvoff, normalized: bool = False,
                   span: int = 0):
    """``(pv, m, l)`` partials, or with ``normalized`` the finished
    ``o [B, Tq, H, dv]`` in q's dtype (kernel ``flash_fwd_o``).  ``k``
    and ``v`` may have fewer heads than ``q``: query head ``h`` reads
    K/V head ``h // (H / H_kv)``, through the K/V blocks' index map, so
    no head is repeated in memory.  ``span`` > 0 is a sliding window
    (causal, query i sees keys i - span < j <= i): the grid's KV axis
    covers a Q block's reach and no more."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale, causal, block_q, block_k, interpret = static[:5]
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    assert h_kv * group == h, (h, h_kv)
    assert causal or not span, "a sliding window is a causal mask"
    dv = v.shape[-1]  # its own size under latent attention (qk 192, v 128)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    bh = b * h

    # Unaligned seq lengths: pad to block multiples and mask.  Padded Q
    # rows are sliced off the outputs; padded KV rows are excluded in
    # the kernel via the kvend position bound (a scalar-prefetch arg, so
    # the padded and exact cases share one compiled kernel per shape).
    tq_pad = -tq % block_q
    tk_pad = -tk % block_k
    kv_padded = tk_pad != 0
    q = _pad_seq(q, tq_pad)
    k = _pad_seq(k, tk_pad)
    v = _pad_seq(v, tk_pad)
    tq_p, tk_p = tq + tq_pad, tk + tk_pad

    qt = q.transpose(0, 2, 1, 3).reshape(bh, tq_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h_kv, tk_p, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h_kv, tk_p, dv)
    kvend = kvoff + tk

    # The kernel body is written batched over G fused (b,h) pairs per
    # grid step (DMLC_FLASH_BH_BLOCK for sweeps), but G=1 is the
    # default: fusing pairs forces smaller q/kv blocks (the f32
    # [G,bq,bk] softmax intermediates hit the 16 MB scoped-VMEM cap),
    # and every (G>1, smaller-block) point lost to (G=1, 1024^2) on the
    # flagship step when it was swept (not swept again on the
    # benchmark's chip: ROADMAP A11).
    gmax = get_env("DMLC_FLASH_BH_BLOCK", 0) or 1
    g = 1
    # grouped heads and a window map each (b, h) pair to K/V blocks of
    # its own: nothing to fuse
    while g * 2 <= gmax and bh % (g * 2) == 0 and group == 1 and not span:
        g *= 2

    n_kv = tk_p // block_k
    if group == 1 and not span:
        kv_steps = n_kv

        def kv_at(bi, qi, kj, *_):
            return (bi, kj, 0)
    else:
        # the new callers' form: a step whose block is wholly masked
        # (above the diagonal, behind the window, past a short walk's
        # end) names the block its neighbour names, so the pipeline
        # fetches nothing for it; under a window the KV axis is a Q
        # block's reach, ``span + block_q`` keys, whatever T is
        kv_steps = min(n_kv, (span + block_q - 2) // block_k + 2) \
            if span else n_kv

        def kv_at(bi, qi, kj, qoff_ref, kvoff_ref, _):
            first_q = qoff_ref[0] + qi * block_q
            if span:
                kj = kj + _first_kv_block(first_q, kvoff_ref[0], span,
                                          block_k)
            if causal:
                kj = jnp.minimum(kj, jnp.maximum(
                    first_q + block_q - 1 - kvoff_ref[0], 0) // block_k)
            return (bi // group, jnp.minimum(kj, n_kv - 1), 0)

    grid = (bh // g, tq_p // block_q, kv_steps)
    in_specs = [
        pl.BlockSpec((g, block_q, d), lambda bi, qi, kj, *_: (bi, qi, 0)),
        pl.BlockSpec((g, block_k, d), kv_at),
        pl.BlockSpec((g, block_k, dv), kv_at),
    ]
    operands = (qoff, kvoff, kvend, qt, kt, vt)
    kernel_static = dict(block_q=block_q, block_k=block_k, causal=causal,
                         kv_padded=kv_padded, scale=scale,
                         interpret=bool(interpret), span=int(span))
    if normalized:
        o = pl.pallas_call(
            functools.partial(_kernel_o, n_kv=grid[2], **kernel_static),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
                out_specs=pl.BlockSpec(
                    (g, block_q, dv), lambda bi, qi, kj, *_: (bi, qi, 0)),
                scratch_shapes=[pltpu.VMEM((g, block_q, dv), jnp.float32),
                                pltpu.VMEM((g, block_q, 8), jnp.float32),
                                pltpu.VMEM((g, block_q, 8), jnp.float32)],
            ),
            out_shape=_out_struct((bh, tq_p, dv), q.dtype, *operands),
            name="flash_fwd_o",
            interpret=interpret,
        )(*operands)
        return o.reshape(b, h, tq_p, dv).transpose(0, 2, 1, 3)[:, :tq]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((g, block_q, dv), lambda bi, qi, kj, *_: (bi, qi, 0)),
            pl.BlockSpec((g, block_q, 8), lambda bi, qi, kj, *_: (bi, qi, 0)),
            pl.BlockSpec((g, block_q, 8), lambda bi, qi, kj, *_: (bi, qi, 0)),
        ],
    )
    pv, m, l = pl.pallas_call(
        functools.partial(_kernel, **kernel_static),
        grid_spec=grid_spec,
        out_shape=[
            _out_struct((bh, tq_p, dv), jnp.float32, *operands),
            _out_struct((bh, tq_p, 8), jnp.float32, *operands),
            _out_struct((bh, tq_p, 8), jnp.float32, *operands),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(*operands)

    pv = pv.reshape(b, h, tq_p, dv).transpose(0, 2, 1, 3)[:, :tq]
    m = m[..., 0].reshape(b, h, tq_p)[:, :, :tq]
    l = l[..., 0].reshape(b, h, tq_p)[:, :, :tq]
    return pv, m, l


# ---------------------------------------------------------------------
# FlashAttention backward: two passes over saved (o, lse), no T×T matrix.
#
#   P   = exp(S - lse)           (normalized probabilities, recomputed)
#   dV  = Pᵀ dO
#   dS  = P ∘ (dO Vᵀ - delta)    with delta = rowsum(dO ∘ O)
#   dQ  = scale · dS K
#   dK  = scale · dSᵀ Q
# ---------------------------------------------------------------------

def _dispatch_masked_step(pl, step, qi, j, block_q: int, block_k: int,
                          causal: bool, kv_padded: bool, kvend_ref,
                          qoff=0, kvoff=0, span: int = 0):
    """Block-level mask classification (exact), shared by the forward
    and backward kernels: skip fully-invisible blocks, run the
    mask-free body on blocks the mask could not touch (all-keep), and
    pay the per-element iota/compare/select chain only on
    diagonal/padded-tail blocks — for every other visible block the
    mask would be all-True, and skipping it removes ~half the VPU work
    per step.  The forward passes its scalar-prefetch global offsets;
    the backward runs in local positions (offsets 0).  Under a
    sliding window of ``span`` keys (forward only) a block wholly
    behind the window is skipped like one above the diagonal, and one
    the window's edge crosses is a boundary block."""
    first_q = qoff + qi * block_q
    last_q = first_q + block_q - 1
    kb_first = kvoff + j * block_k
    kb_last = kb_first + block_k - 1
    visible = last_q >= kb_first if causal else None
    boundary = None
    if causal:
        boundary = kb_last > first_q
    if span:
        # (a short walk's steps past the last KV block name blocks
        # that do not exist)
        visible = visible & (kb_last > first_q - span) & (
            kb_first < kvend_ref[0])
        boundary = boundary | (kb_first <= last_q - span)
    if kv_padded:
        pad = kb_last >= kvend_ref[0]
        boundary = pad if boundary is None else boundary | pad
    if boundary is None:
        step(False)
        return
    clean = jnp.logical_not(boundary)
    if visible is not None:
        clean = clean & visible
        boundary = boundary & visible
    pl.when(clean)(lambda: step(False))
    pl.when(boundary)(lambda: step(True))


def _bwd_dkv_kernel(kvend_ref, q_ref, do_ref, k_ref, v_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, block_q: int,
                    block_k: int, causal: bool, kv_padded: bool,
                    scale: float):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)   # KV block (the accumulator's home)
    qi = pl.program_id(2)  # Q step (innermost: pipelined)

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    def step(masked: bool):
        kb = k_ref[0]                     # [block_k, D]
        vb = v_ref[0]
        qb = q_ref[0]                     # [block_q, D]
        dob = do_ref[0]
        lse = lse_ref[0][:, 0]            # [block_q]
        dlt = delta_ref[0][:, 0]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        p = jnp.exp(s - lse[:, None])
        if masked:
            keep = None
            if causal:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = j * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                keep = q_pos >= k_pos
            if kv_padded:
                kp = j * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                in_range = kp < kvend_ref[0]
                keep = in_range if keep is None else keep & in_range
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
        dv_ref[0] += jax.lax.dot_general(
            p, dob.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - dlt[:, None])
        dk_ref[0] += scale * jax.lax.dot_general(
            ds, qb.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]

    _dispatch_masked_step(pl, step, qi, j, block_q, block_k, causal,
                          kv_padded, kvend_ref)


def _bwd_dq_kernel(kvend_ref, q_ref, do_ref, k_ref, v_ref, lse_ref,
                   delta_ref, dq_ref, *, block_q: int, block_k: int,
                   causal: bool, kv_padded: bool, scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)  # Q block (the accumulator's home)
    j = pl.program_id(2)   # KV step (innermost: pipelined)

    @pl.when(j == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    def step(masked: bool):
        qb = q_ref[0]                      # [block_q, D]
        dob = do_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        lse = lse_ref[0][:, 0]             # [block_q]
        dlt = delta_ref[0][:, 0]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, None])
        if masked:
            keep = None
            if causal or kv_padded:
                k_pos = j * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
            if causal:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                keep = q_pos >= k_pos
            if kv_padded:
                in_range = k_pos < kvend_ref[0]
                keep = in_range if keep is None else keep & in_range
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dlt[:, None])
        dq_ref[0] += scale * jax.lax.dot_general(
            ds, kb.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_masked_step(pl, step, qi, j, block_q, block_k, causal,
                          kv_padded, kvend_ref)


def _flash_backward(static, q, k, v, o, lse, do):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale, causal, block_q, block_k, interpret = static[:5]
    if len(static) > 5:  # separately-tuned backward blocks
        block_q, block_k = static[5], static[6]
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    bh = b * h

    # delta = rowsum(dO ∘ O), [B, T, H] — cheap, fused by XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    tq_pad = -tq % block_q
    tk_pad = -tk % block_k
    kv_padded = tk_pad != 0
    q = _pad_seq(q, tq_pad)
    do = _pad_seq(do, tq_pad)
    k = _pad_seq(k, tk_pad)
    v = _pad_seq(v, tk_pad)
    tq_p, tk_p = tq + tq_pad, tk + tk_pad

    qt = q.transpose(0, 2, 1, 3).reshape(bh, tq_p, d)
    dot = do.transpose(0, 2, 1, 3).reshape(bh, tq_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(bh, tk_p, d)
    vt = v.transpose(0, 2, 1, 3).reshape(bh, tk_p, d)
    # lse/delta: [B,H,Tq]-like → [bh, tq_p, 8] lane-broadcast; padded Q
    # rows get lse=+BIG so exp(S - lse) underflows to exactly 0 and they
    # contribute nothing to dK/dV
    lse_p = jnp.pad(lse.reshape(bh, tq), ((0, 0), (0, tq_pad)),
                    constant_values=_POS_BIG)
    delta_p = jnp.pad(delta.transpose(0, 2, 1).reshape(bh, tq),
                      ((0, 0), (0, tq_pad)))
    lse8 = jnp.broadcast_to(lse_p[:, :, None], (bh, tq_p, 8))
    delta8 = jnp.broadcast_to(delta_p[:, :, None], (bh, tq_p, 8))
    kvend = jnp.asarray([tk], jnp.int32)

    # dkv grid (bh, kv, q): accumulators live in the kv-indexed output
    # blocks, revisited across the innermost q steps
    q_of_q = pl.BlockSpec((1, block_q, d), lambda bi, kj, qi, *_: (bi, qi, 0))
    k_of_kv = pl.BlockSpec((1, block_k, d), lambda bi, kj, qi, *_: (bi, kj, 0))
    s_of_q = pl.BlockSpec((1, block_q, 8), lambda bi, kj, qi, *_: (bi, qi, 0))
    operands = (kvend, qt, dot, kt, vt, lse8, delta8)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, kv_padded=kv_padded, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, tk_p // block_k, tq_p // block_q),
            in_specs=[q_of_q, q_of_q, k_of_kv, k_of_kv, s_of_q, s_of_q],
            out_specs=[k_of_kv, k_of_kv],
        ),
        out_shape=[
            _out_struct((bh, tk_p, d), jnp.float32, *operands),
            _out_struct((bh, tk_p, d), jnp.float32, *operands),
        ],
        # bh and the accumulator's home dim are independent; only the
        # innermost (accumulating) dim is order-dependent: faster than
        # leaving the semantics unspecified when it was tried.  (The fwd
        # kernel regressed with the same hint, so it stays plain.)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_dkv",
        interpret=interpret,
    )(*operands)

    # dq grid (bh, q, kv): accumulator in the q-indexed output block
    q_of_q2 = pl.BlockSpec((1, block_q, d), lambda bi, qi, kj, *_: (bi, qi, 0))
    k_of_kv2 = pl.BlockSpec((1, block_k, d), lambda bi, qi, kj, *_: (bi, kj, 0))
    s_of_q2 = pl.BlockSpec((1, block_q, 8), lambda bi, qi, kj, *_: (bi, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, kv_padded=kv_padded, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, tq_p // block_q, tk_p // block_k),
            in_specs=[q_of_q2, q_of_q2, k_of_kv2, k_of_kv2, s_of_q2,
                      s_of_q2],
            out_specs=q_of_q2,
        ),
        out_shape=_out_struct((bh, tq_p, d), jnp.float32, *operands),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_dq",
        interpret=interpret,
    )(*operands)

    def unpack(x, t):
        return x.reshape(b, h, -1, d).transpose(0, 2, 1, 3)[:, :t]

    dq = unpack(dq, tq).astype(q.dtype)
    dk = unpack(dk, tk).astype(k.dtype)
    dv = unpack(dv, tk).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attn(static, q, k, v):
    o, _ = _flash_attn_impl(static, q, k, v)
    return o


def _flash_attn_impl(static, q, k, v):
    from jax.ad_checkpoint import checkpoint_name

    zero = jnp.zeros(1, jnp.int32)
    pv, m, l = _flash_forward(static, q, k, v, zero, zero)
    lsafe = jnp.maximum(l, 1e-20)                         # [B,H,Tq]
    o = (pv / jnp.transpose(lsafe, (0, 2, 1))[..., None]).astype(q.dtype)
    lse = m + jnp.log(lsafe)
    # named for remat policies: saving (o, lse) lets jax.checkpoint skip
    # re-running the forward kernel in the backward pass (they are the
    # custom_vjp residuals) — see models.TransformerConfig.remat_policy
    return (checkpoint_name(o, "flash_o"),
            checkpoint_name(lse, "flash_lse"))


def _flash_attn_fwd(static, q, k, v):
    o, lse = _flash_attn_impl(static, q, k, v)
    return o, (q, k, v, o, lse)


def _flash_attn_bwd(static, res, do):
    q, k, v, o, lse = res
    return _flash_backward(static, q, k, v, o, lse, do)


_flash_attn.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def lax_attention(q, k, v, *, scale: float, causal: bool = True,
                  span: int = 0, q_offset=None):
    """The lax twin of the forward-only call: q [B, Tq, H, D] at global
    positions ``q_offset + i`` against k, v [B, Tk, H_kv, D] at ``j``;
    query head ``h`` reads K/V head ``h // (H / H_kv)``; ``span`` > 0
    keeps keys ``i - span < j <= i``.  Dense softmax, f32 scores."""
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1], k.shape[2]
    qg = q.reshape(b, tq, h_kv, h // h_kv, d)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        i = ((0 if q_offset is None else q_offset)
             + jnp.arange(tq))[:, None]
        j = jnp.arange(tk)[None, :]
        keep = j <= i
        if span:
            keep = keep & (i - j < span)
        s = jnp.where(keep, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, tq, h, v.shape[-1]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_fwd_only(static, q, k, v, qoff):
    """The forward alone (kernel ``flash_fwd_o``), for what the
    backward kernels do not cover: a value head size of its own,
    grouped heads, a sliding window, a query offset."""
    return _flash_forward(static[:-1], q, k, v, qoff,
                          jnp.zeros(1, jnp.int32), normalized=True,
                          span=static[-1])


def _flash_fwd_only_fwd(static, q, k, v, qoff):
    return _flash_fwd_only(static, q, k, v, qoff), None


def _flash_fwd_only_bwd(static, res, do):
    raise NotImplementedError(
        "flash attention with grouped heads, a sliding window, a query "
        "offset or a value head size of its own is forward-only: the "
        "backward kernels take none of them, and a causal gradient in "
        "their place would be wrong")


_flash_fwd_only.defvjp(_flash_fwd_only_fwd, _flash_fwd_only_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False, span: int = 0,
                    q_offset=None):
    """Standalone exact attention via the flash kernels (single device).

    q/k/v: [B, T, H, D].  Differentiable where q, k and v share one
    head count and size and the mask is the plain causal one.  k and v
    may have fewer heads than q (grouped-query attention: no head is
    repeated in memory), v a head size of its own (latent attention's
    prefill), ``span`` > 0 makes the mask a sliding window (query i
    sees keys i - span < j <= i; blocks behind it cost nothing), and
    ``q_offset`` (a traced int) places q's rows at ``q_offset + i``
    against keys at ``j`` (a prompt's rows walked in chunks against its
    whole K/V): each of these is forward-only, and differentiating it
    raises.  The oracle-equivalent of
    ring_attention_reference with O(T) memory in BOTH directions: the
    backward recomputes P from the saved (o, lse) residuals in blocks
    (dkv + dq kernels) instead of materializing the T×T matrix.

    Default block sizes: uniform 1024x1024 for forward AND backward
    (clamped to T); DMLC_FLASH_BLOCK_Q/K and DMLC_FLASH_BWD_BLOCK_Q/K
    override for sweeps (read at trace time).  They were chosen by a
    sweep on another v5e before the benchmark existed and have not
    been swept on its chip (ROADMAP A11).  What that chip read of the
    forward kernel at these blocks (scripts/time_flash_fwd.py, PR 37):
    Command A+'s 8,192-row chunk against 32,768 keys 110 ms (70% of
    its roofline; 21 ms and 52% under span=4096), A.X-K1's T=16,384
    54 ms (52%: qk 192 fills the matrix unit as 256 would), the
    flagship's T=2,048 0.19 ms (46%), a ring step of 4,096 x 4,096 at
    blocks of 512 1.1-1.8 ms (33-39%).
    """

    from .. import telemetry

    b, tq, h, d = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # trace-time accounting: attention FLOPs are static in the shapes
    # (2 matmuls of [tq,tk]x[tk,d] per head; causal halves the visited
    # area), so the counter is exact per compiled call — MFU math reads
    # it straight off /metrics without re-deriving shapes
    dv = v.shape[-1]
    flops = 2.0 * b * h * tq * tk * (d + dv) * (0.5 if causal else 1.0)
    telemetry.inc("flash", "fwd_calls")
    telemetry.inc("flash", "fwd_flops", flops)
    telemetry.observe("flash", "seq_len_q", float(tq),
                      bounds=tuple(float(2 ** i) for i in range(22)))
    with telemetry.span("flash_attention.trace", stage="flash",
                        args={"b": int(b), "t_q": int(tq), "t_kv": int(tk),
                              "heads": int(h), "d": int(d),
                              "causal": bool(causal)}):
        pass
    # explicit caller blocks bind BOTH passes (a caller sizing for VMEM
    # must not get surprise-larger backward tiles); env/defaults fill
    # whatever remains
    bwd_q = block_q if block_q is not None \
        else get_env("DMLC_FLASH_BWD_BLOCK_Q", 0) or 1024
    bwd_k = block_k if block_k is not None \
        else get_env("DMLC_FLASH_BWD_BLOCK_K", 0) or 1024
    if block_q is None:
        block_q = get_env("DMLC_FLASH_BLOCK_Q", 0) or 1024
    if block_k is None:
        block_k = get_env("DMLC_FLASH_BLOCK_K", 0) or 1024
    static = (float(scale), bool(causal), int(block_q), int(block_k),
              bool(interpret), int(bwd_q), int(bwd_k))
    if dv != d or k.shape[2] != h or span or q_offset is not None:
        qoff = jnp.asarray(0 if q_offset is None else q_offset,
                           jnp.int32).reshape(1)
        return _flash_fwd_only(static[:5] + (int(span),), q, k, v, qoff)
    return _flash_attn(static, q, k, v)
