"""Ring attention: exact attention over sequence shards via ICI neighbour
exchange.

Long-context capability is new relative to the reference (dmlc-core
predates it — SURVEY.md §5); what carries over is the partitioning
contract: the sequence dimension is sharded by the same
(part_index, num_parts) scheme InputSplit uses for bytes
(/root/reference/src/io/input_split_base.cc:30-64), with part_index =
mesh coordinate along the ``sp`` axis.

Algorithm: each sp shard holds Q for its sequence block and rotates the
K/V blocks around the ring with `lax.ppermute`, folding each block into a
flash-attention-style online softmax (running max + denominator), so the
full-sequence result is exact while peak memory stays O(T/sp).  The KV
rotation overlaps with compute at the XLA level (async collective
permute on TPU).  On the kernel path the ring has a VJP of its own: the
forward keeps (o, lse), and the backward walks the ring once more with
the flash backward kernels at global offsets, the dK / dV accumulators
travelling with their K/V block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import match_vma as _match_vma

_NEG_BIG = -1e30


from ..ops import flash_attention as _flash  # noqa: E402
# canonical lax (pv, m, l) block attend — one implementation, shared with
# the flash kernel's VJP twin so the two can never diverge
from ..ops.flash_attention import lax_block_attend as _block_attend  # noqa: E402


def ring_attention(
    q,
    k,
    v,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    impl: str = "auto",
):
    """Exact multi-head attention over a ring of sequence shards.

    Call inside `jax.shard_map` with q/k/v already sequence-sharded:
    shapes [B, T_local, H, D] where T_global = T_local * axis_size(sp).
    Head layouts may additionally be tensor-sharded; this function only
    touches the sequence dimension.

    ``impl``: 'auto' routes each ring step through the Pallas flash
    kernel (ops/flash_attention) or the pure-lax reference as
    ops/dispatch decides; 'flash' forces the kernel (interpret mode
    off-TPU, for tests); 'lax' forces the reference.
    """
    n = lax.axis_size(axis_name)
    b, t_local, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    from .. import telemetry
    from ..ops import dispatch

    # trace-time accounting (the ring loop runs device-side): each of
    # the n ring steps rotates the full local K+V block over ICI, so
    # bytes_rotated = 2 * |k| * (n - 1) per call — the DCN/ICI budget a
    # capacity planner reads off /metrics
    kv_bytes = float(2 * k.size * k.dtype.itemsize)
    telemetry.inc("ring_attention", "calls")
    telemetry.inc("ring_attention", "bytes_rotated",
                  kv_bytes * max(0, n - 1))
    telemetry.observe("ring_attention", "kv_block_bytes", kv_bytes,
                      bounds=tuple(64.0 * 2.0 ** i for i in range(28)))
    with telemetry.span("ring_attention.trace", stage="ring",
                        args={"steps": int(n), "t_local": int(t_local),
                              "heads": int(h), "kv_block_bytes":
                              int(kv_bytes), "impl": impl}):
        pass

    if impl not in ("auto", "flash", "lax"):
        raise ValueError(f"unknown ring_attention impl {impl!r}")
    mode = dispatch.choose(_flash.supports(q.shape, k.shape), impl)
    use_flash = mode != dispatch.LAX
    interpret = mode == dispatch.INTERPRET

    if n == 1 and use_flash:
        # degenerate ring (sp axis of size 1 — e.g. dp-only meshes): the
        # standalone kernel path is strictly better (no rotation)
        return _flash.flash_attention(q, k, v, causal=causal, scale=scale,
                                      interpret=interpret)
    if use_flash:
        return _ring_flash((axis_name, bool(causal), float(scale),
                            interpret), q, k, v)
    acc, _, l = _ring_forward(q, k, v, axis_name, causal, scale, None)
    return _normalized(acc, l, q.dtype)


def _ring_forward(q, k, v, axis_name, causal, scale, interpret):
    """The ring's loop: (acc [B, T, H, D], m, l [B, H, T]) float32 of my
    Q block against every rank's K/V block, each step through the flash
    kernel or (``interpret`` None) the pure-lax block attend."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    q_pos = jnp.arange(t_local)  # local positions; global = blk*t_local + pos
    acc0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    m0 = jnp.full((b, h, t_local), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    # loop carries become device-varying (they fold in varying K/V blocks);
    # under VMA-checked shard_map the initial values must carry that type
    acc0, m0, l0 = (_match_vma(a, q) for a in (acc0, m0, l0))

    def step(i, carry):
        acc, m, l, k_blk, v_blk = carry
        src = (my - i) % n  # ring position the held KV block originated from
        if interpret is not None:
            # the kernel takes the global offsets as scalar-prefetch args,
            # so one compiled kernel serves every ring step
            pv, bm, bl = _flash.block_attend_flash(
                q, k_blk, v_blk, scale=scale, causal=causal,
                q_offset=my * t_local, kv_offset=src * t_local,
                interpret=interpret)
        else:
            if causal:
                # global causal mask between my Q block and the src KV block
                gq = my * t_local + q_pos[:, None]
                gk = src * t_local + q_pos[None, :]
                mask = gq >= gk
            else:
                mask = None
            pv, bm, bl = _block_attend(q, k_blk, v_blk, scale=scale, mask=mask)
        m_new = jnp.maximum(m, bm)
        corr = jnp.exp(m - m_new)          # rescale old accumulator
        bcor = jnp.exp(bm - m_new)         # rescale this block
        l_new = l * corr + bl * bcor
        acc_new = (
            acc * jnp.transpose(corr, (0, 2, 1))[..., None]
            + pv * jnp.transpose(bcor, (0, 2, 1))[..., None]
        )
        k_next = lax.ppermute(k_blk, axis_name, _ring_perm(n))
        v_next = lax.ppermute(v_blk, axis_name, _ring_perm(n))
        return acc_new, m_new, l_new, k_next, v_next

    acc, m, l, _, _ = lax.fori_loop(0, n, step, (acc0, m0, l0, k, v))
    return acc, m, l


def _ring_perm(n):
    return [(j, (j + 1) % n) for j in range(n)]


def _normalized(acc, l, dtype):
    denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
    return (acc / denom).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_flash(static, q, k, v):
    return _ring_flash_out(static, q, k, v)[0]


def _ring_flash_out(static, q, k, v):
    """The kernel path's forward: o in q's dtype and lse = m + log(l)
    float32 [B, H, T], named as ops.flash_attention's one-chip call
    names them, so that the save_flash remat policy keeps both and the
    backward does not run the ring (and its rotations) again."""
    from jax.ad_checkpoint import checkpoint_name

    axis_name, causal, scale, interpret = static
    acc, m, l = _ring_forward(q, k, v, axis_name, causal, scale, interpret)
    lse = m + jnp.log(jnp.maximum(l, 1e-20))
    return (checkpoint_name(_normalized(acc, l, q.dtype), "flash_o"),
            checkpoint_name(lse, "flash_lse"))


def _ring_flash_fwd(static, q, k, v):
    o, lse = _ring_flash_out(static, q, k, v)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(static, res, do):
    """The ring again, backwards: my (q, dO, lse, delta) laid out once,
    and at ring step i the kernels ``flash_dq`` / ``flash_dkv`` against
    the K/V block I hold, at its global offset.  dQ accumulates here;
    dK / dV accumulate in float32 buffers that travel with their K/V
    block and arrive home after the n-th rotation, which carries them
    alone."""
    from .. import telemetry

    axis_name, causal, scale, interpret = static
    q, k, v, o, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    t = q.shape[1]
    block_q, block_k = (min(x, t) for x in _flash.bwd_blocks())
    kernels = functools.partial(
        _flash.bwd_kernels, (scale, causal, block_q, block_k, interpret),
        (my * t).astype(jnp.int32).reshape(1))
    q_side = _flash.bwd_q_side(q, o, lse, do, block_q)
    perm = _ring_perm(n)

    def grads(i, acc, kt, vt):
        telemetry.inc("flash", "ring_step_bwd_calls")
        kvoff = (((my - i) % n) * t).astype(jnp.int32).reshape(1)
        return kernels(kvoff, t, *q_side, kt, vt, acc=acc)

    def step(i, carry):
        acc, kt, vt = carry[:3], carry[3], carry[4]
        dq, dk, dv = grads(i, acc, kt, vt)
        dk, dv, kt, vt = (lax.ppermute(x, axis_name, perm)
                          for x in (dk, dv, kt, vt))
        return dq, dk, dv, kt, vt

    kt, vt = (_flash.heads_layout(x, block_k) for x in (k, v))
    acc0 = tuple(_match_vma(jnp.zeros(x.shape, jnp.float32), x)
                 for x in (q_side[0], kt, vt))
    *acc, kt, vt = lax.fori_loop(0, n - 1, step, (*acc0, kt, vt))
    dq, dk, dv = grads(n - 1, tuple(acc), kt, vt)
    dk, dv = (lax.ppermute(x, axis_name, perm) for x in (dk, dv))
    return tuple(_flash.heads_unlayout(x, like)
                 for x, like in ((dq, q), (dk, k), (dv, v)))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention_reference(q, k, v, *, causal: bool = True, scale=None):
    """Unsharded full attention — the correctness oracle for ring_attention.

    q/k/v: [B, T, H, D] (full sequence on one device).
    """
    b, t, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def make_sharded_ring_attention(mesh, *, causal: bool = True,
                                impl: str = "auto"):
    """Wrap ring_attention in shard_map over (sp sequence, tp heads).

    The returned callable is span-wrapped (``ring_attention.run``) so
    host-side dispatch shows on the flight-recorder timeline."""
    from jax.sharding import PartitionSpec as P

    from .. import telemetry

    spec = P(None, "sp", "tp", None)
    fn = functools.partial(ring_attention, axis_name="sp", causal=causal,
                          impl=impl)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    sp = int(mesh.shape["sp"])

    def run(q, k, v):
        with telemetry.span("ring_attention.run", stage="ring",
                            args={"sp": sp, "t": int(q.shape[1])}):
            return mapped(q, k, v)

    return run
