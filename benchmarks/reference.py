"""The plain reference that decides ``correct``.

The flagship block in straightforward ``jax.numpy``, float32, with
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes): RMSNorm, RoPE, causal multi-head
attention computed one block of queries at a time against the whole
context, SwiGLU, untied unembed, mean cross-entropy.  No kernels, no
cache, no batching across sequences, and no import from
``dmlc_tpu.models`` or ``dmlc_tpu.ops``.

It reads the program's parameter tree as stored (bf16 values, which a
cast to float32 represents exactly):

  embed [V, E]   unembed [E, V]   ln_f [E]
  blocks: ln1, ln2 [S, L/S, E]; wq, wk, wv [S, L/S, E, H, D];
          wo [S, L/S, H, D, E]; gate [S, L/S, E, X];
          w_in, w_gate [S, L/S, X, E, F]; w_out [S, L/S, X, F, E]

Departures from a textbook block, each the program's own definition:
RoPE rotates the two halves of a head (not interleaved pairs), theta
10000; RMSNorm's epsilon is 1e-6; with one expert (X = 1) the gated
mixture is the expert itself, and this reference supports only that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512
ROPE_THETA = 10000.0
RMS_EPS = 1e-6


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + RMS_EPS) * _f32(scale)


def _rope(x, positions):
    """x [T, H, D], positions [T]."""
    half = x.shape[-1] // 2
    freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _causal_attention(q, k, v):
    """q, k, v [T, H, D] -> [T, H, D]; T is a multiple of the block or
    shorter than one."""
    t, h, d = q.shape
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    key_pos = jnp.arange(t)

    def block(start):
        qs = lax.dynamic_slice_in_dim(q, start, qb, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qs, k) * (d ** -0.5)
        visible = key_pos[None, :] <= (start + jnp.arange(qb))[:, None]
        s = jnp.where(visible[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(block, jnp.arange(0, t, qb))
    return out.reshape(t, h, d)


def _layers(blocks):
    """[S, L/S, ...] stacks as one [L, ...] stack."""
    if blocks["gate"].shape[-1] != 1:
        raise ValueError("the reference covers the dense block only "
                         f"(n_experts = 1), got {blocks['gate'].shape[-1]}")
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), blocks)


def hidden_states(params, ids, quantize=None):
    """One sequence ``ids`` [T] -> final-norm hidden states [T, E].

    ``quantize`` (a dtype) rounds every matmul operand to it first: the
    control that shows the tolerances would catch a lower precision."""
    def op(x):
        return _f32(x.astype(quantize)) if quantize is not None else x

    t = ids.shape[0]
    positions = jnp.arange(t)
    x = _f32(jnp.take(params["embed"], ids, axis=0))

    def layer(x, p):
        xn = op(_rms_norm(x, p["ln1"]))
        q = jnp.einsum("te,ehd->thd", xn, op(_f32(p["wq"])))
        k = jnp.einsum("te,ehd->thd", xn, op(_f32(p["wk"])))
        v = jnp.einsum("te,ehd->thd", xn, op(_f32(p["wv"])))
        o = _causal_attention(op(_rope(q, positions)),
                              op(_rope(k, positions)), op(v))
        x = x + jnp.einsum("thd,hde->te", op(o), op(_f32(p["wo"])))
        xn = op(_rms_norm(x, p["ln2"]))
        hidden = (jnp.einsum("te,ef->tf", xn, op(_f32(p["w_in"][0])))
                  * jax.nn.silu(jnp.einsum("te,ef->tf", xn,
                                           op(_f32(p["w_gate"][0])))))
        x = x + jnp.einsum("tf,fe->te", op(hidden),
                           op(_f32(p["w_out"][0])))
        return x, None

    x, _ = lax.scan(layer, x, _layers(params["blocks"]))
    return _rms_norm(x, params["ln_f"])


def _logits(params, h, quantize=None):
    w = _f32(params["unembed"])
    if quantize is not None:
        h, w = _f32(h.astype(quantize)), _f32(w.astype(quantize))
    return jnp.einsum("te,ev->tv", h, w)


@functools.partial(jax.jit, static_argnames=("quantize",))
def mean_loss(params, ids, labels, quantize=None):
    """Mean cross-entropy over ``ids``/``labels`` [B, T], one sequence
    at a time and one block of positions at a time (the [T, V] logits
    of a long sequence are never whole in memory)."""
    with jax.default_matmul_precision("highest"):
        def one(args):
            seq, lab = args
            h = hidden_states(params, seq, quantize)
            qb = min(Q_BLOCK, seq.shape[0])

            def block(start):
                hs = lax.dynamic_slice_in_dim(h, start, qb, axis=0)
                ls = lax.dynamic_slice_in_dim(lab, start, qb, axis=0)
                logits = _logits(params, hs, quantize)
                lse = jax.nn.logsumexp(logits, axis=-1)
                hit = jnp.take_along_axis(logits, ls[:, None], axis=-1)
                return jnp.sum(lse - hit[:, 0])

            return jnp.sum(lax.map(block, jnp.arange(0, seq.shape[0], qb)))

        total = jnp.sum(lax.map(one, (ids, labels)))
    return total / ids.size


@functools.partial(jax.jit, static_argnames=("quantize",))
def logits_at(params, ids, positions, quantize=None):
    """Logits [n, V] at ``positions`` [n] of one sequence ``ids`` [T],
    each conditioned on everything before it (teacher forcing: position
    i scores token i + 1)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, quantize)
        return _logits(params, jnp.take(h, positions, axis=0), quantize)
