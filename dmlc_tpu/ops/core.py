"""Shard-aware core ops.

Every op takes a `ShardAxes` describing which mesh axes (if any) the
relevant dimensions are sharded over; with all axes None the same code is
the single-device oracle used by tests and by the single-chip `entry()`
path.  Collectives are the only difference between the two — the math is
identical, which is what makes the sharded path testable against the
unsharded one.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class ShardAxes:
    """Mesh axis names for each parallelism flavour (None = unsharded)."""

    tp: Optional[str] = None  # tensor: heads / ffn hidden / vocab
    sp: Optional[str] = None  # sequence: ring attention blocks
    ep: Optional[str] = None  # expert: MoE expert shards
    pp: Optional[str] = None  # pipeline: layer stages
    dp: Optional[str] = None  # data: batch shards (grad reduction)


import functools


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _pmax_const(x, axis_name):
    """pmax treated as a constant under differentiation (lax.pmax has no
    JVP rule; we only use it for softmax stabilisation where the true
    gradient does not depend on it)."""
    return lax.pmax(x, axis_name)


@_pmax_const.defjvp
def _pmax_const_jvp(axis_name, primals, tangents):
    (x,) = primals
    y = lax.pmax(x, axis_name)
    return y, jnp.zeros_like(y)


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layer_norm(x, scale, eps: float = 1e-5):
    """LayerNorm with a weight and no bias: mean and variance over the
    last axis, in float32."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    return (xc * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope(x, positions, theta: float = 10000.0):
    """Rotary position embedding.  x: [B, T, H, D], positions: [T] global."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # [T, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def embed_lookup(embed_local, ids, axes: ShardAxes):
    """Vocab-sharded embedding lookup: mask out-of-shard ids, psum over tp.

    embed_local: [V_local, E] (tp shard of the table); ids: [...] global.
    """
    v_local = embed_local.shape[0]
    if axes.tp is None:
        return jnp.take(embed_local, ids, axis=0)
    offset = lax.axis_index(axes.tp) * v_local
    local = ids - offset
    in_shard = (local >= 0) & (local < v_local)
    safe = jnp.clip(local, 0, v_local - 1)
    emb = jnp.take(embed_local, safe, axis=0)
    emb = jnp.where(in_shard[..., None], emb, jnp.zeros_like(emb))
    return lax.psum(emb, axes.tp)


def softmax_xent(logits_local, labels, axes: ShardAxes):
    """Cross entropy with vocab-sharded logits.

    logits_local: [..., V_local]; labels: [...] global ids.
    Returns per-token loss [...] (f32), replicated over tp.
    """
    logits_local = logits_local.astype(jnp.float32)
    v_local = logits_local.shape[-1]
    m = jnp.max(logits_local, axis=-1)
    if axes.tp is not None:
        m = _pmax_const(m, axes.tp)
    # m only stabilises the exp; the true lse gradient (softmax) does not
    # depend on it
    m = lax.stop_gradient(m)
    se = jnp.sum(jnp.exp(logits_local - m[..., None]), axis=-1)
    if axes.tp is not None:
        se = lax.psum(se, axes.tp)
    lse = jnp.log(se) + m
    if axes.tp is None:
        correct = jnp.take_along_axis(logits_local, labels[..., None], axis=-1)[..., 0]
    else:
        offset = lax.axis_index(axes.tp) * v_local
        local = labels - offset
        in_shard = (local >= 0) & (local < v_local)
        safe = jnp.clip(local, 0, v_local - 1)
        c = jnp.take_along_axis(logits_local, safe[..., None], axis=-1)[..., 0]
        correct = lax.psum(jnp.where(in_shard, c, 0.0), axes.tp)
    return lse - correct


def swiglu_ffn(x, w_in, w_gate, w_out, axes: ShardAxes, *, reduce: bool = True,
               out_dtype=None):
    """Megatron-style column/row-parallel SwiGLU FFN.

    w_in/w_gate: [E, F_local] (column shards); w_out: [F_local, E] (row
    shard); the single psum over tp happens at the output (row-parallel),
    skipped with reduce=False so callers can batch it with other partial
    sums (MoE).  ``out_dtype`` asks the output product for another type
    than its operands' (a float32 residual stream under bf16 weights).
    """
    from jax.ad_checkpoint import checkpoint_name

    h = jnp.einsum("...e,ef->...f", x, w_in) * jax.nn.silu(
        jnp.einsum("...e,ef->...f", x, w_gate)
    )
    # named for remat policies: saving the [.., F] activation lets the
    # backward skip re-running the in/gate matmuls — the largest single
    # recompute in a rematerialized block (models.TransformerConfig
    # remat_policy='save_flash_mlp')
    h = checkpoint_name(h, "mlp_act")
    y = jnp.einsum("...f,fe->...e", h, w_out,
                   preferred_element_type=out_dtype)
    if reduce and axes.tp is not None:
        y = lax.psum(y, axes.tp)
    return y
