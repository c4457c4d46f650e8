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
permute on TPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import match_vma as _match_vma

_NEG_BIG = -1e30


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
    my = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    from .. import telemetry
    from ..ops import dispatch
    from ..ops import flash_attention as _flash

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
        # standalone kernel path is strictly better — kernel backward
        # (no T×T lax recompute) and save_flash remat policy both apply
        return _flash.flash_attention(q, k, v, causal=causal, scale=scale,
                                      interpret=interpret)

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
        if use_flash:
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
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return acc_new, m_new, l_new, k_next, v_next

    acc, m, l, _, _ = lax.fori_loop(0, n, step, (acc0, m0, l0, k, v))
    denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
    return (acc / denom).astype(q.dtype)


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
