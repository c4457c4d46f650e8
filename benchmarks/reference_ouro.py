"""The plain reference of Ouro's looped block (``model_type: ouro``,
ByteDance's LoopLM).

Straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")``: no kernels, no cache, no batching across sequences, and
no import from ``dmlc_tpu.models`` or ``dmlc_tpu.ops``.  The whole
sequence goes through every layer of every pass at once, and each pass
computes its own keys and values from its own input: nothing is kept
from one pass for the next but the hidden states.  Attention takes
``Q_BLOCK`` queries at a time against the whole context.  Each matrix
is cast to float32 where it is used, one layer at a time and again in
every pass, so the float32 model is never whole in memory (it would be
10.7 GB at Ouro-2.6B's widths).

A token at position i, on the residual stream h (no biases anywhere):

  h      = E[id]
  for t in 1..passes:                 the SAME layers in every pass
    for each layer:
      a  = Attn(RMSNorm(h; ln1)): q = xn W_q, k = xn W_k, v = xn W_v, H
           heads of d (as many K/V heads as query heads), RoPE (theta
           ``rope_theta``, the two halves of a head rotated) on q and k
           at position i in every pass, causal, scale d^-1/2, o W_o
      h  = h + RMSNorm(a; ln1_post)             the sandwich norm
      m  = W_down(silu(W_gate x) * W_up x), x = RMSNorm(h; ln2)
      h  = h + RMSNorm(m; ln2_post)
    h    = RMSNorm(h; ln_f)           closes every pass, starts the next
  logits = h W_lm                     after the last pass (untied head)

RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g over the hidden axis.

It reads the program's tree as stored (models/transformer.py,
``_init_looped_params``): ``embed [V, E]``, ``unembed [E, V]``,
``ln_f``, ``w_exit``, ``b_exit`` and ``layers``, a list of one dict a
layer (``ln1``, ``ln1_post``, ``ln2``, ``ln2_post``, ``wq``, ``wk``,
``wv [H, E, d]``, head-major, ``wo [H, d, E]``, ``w_in``, ``w_gate [E,
F]``, ``w_out [F, E]``).  Every width comes from the tree's shapes; the
passes, RoPE's theta and the norms' epsilon are :class:`Spec`, held to
``configs/ouro-2.6b-serve.json`` by tests/test_ouro_family.py.

Departures from the published model (``modeling_ouro.py``): the exit
gate lambda_t = sigmoid(h . w_exit + b_exit) after each pass is not
applied.  At ``early_exit_threshold`` 1 it decides nothing, every
token runs all passes and the logits are the last pass's, so the gate
is in the tree and computed by no one.  The option of one K/V cache
shared across passes, which the family's paper offers at inference, is
not this model either: each pass attends its own keys.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512


class Spec(NamedTuple):
    """What the parameter tree does not carry."""
    passes: int = 4               # total_ut_steps
    rope_theta: float = 1e6
    norm_eps: float = 1e-6        # rms_norm_eps


OURO = Spec()


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, positions, theta):
    """x [T, H, D] at positions [T], halves rotated."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = _f32(positions)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _causal_attention(q, k, v):
    """q, k, v [T, H, D] -> [T, H, D], a block of queries at a time; T
    is a multiple of the block or shorter than one."""
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

    return lax.map(block, jnp.arange(0, t, qb)).reshape(t, h, d)


def _layer(x, p, positions, op, spec: Spec):
    """One layer on the stream x [T, E]: attention and the SwiGLU, each
    addend normed before it joins."""
    xn = op(_rms_norm(x, p["ln1"], spec.norm_eps))
    q = jnp.einsum("te,hed->thd", xn, op(_f32(p["wq"])))
    k = jnp.einsum("te,hed->thd", xn, op(_f32(p["wk"])))
    v = jnp.einsum("te,hed->thd", xn, op(_f32(p["wv"])))
    o = _causal_attention(op(_rope(q, positions, spec.rope_theta)),
                          op(_rope(k, positions, spec.rope_theta)), op(v))
    a = jnp.einsum("thd,hde->te", op(o), op(_f32(p["wo"])))
    x = x + _rms_norm(a, p["ln1_post"], spec.norm_eps)
    xn = op(_rms_norm(x, p["ln2"], spec.norm_eps))
    hidden = (xn @ op(_f32(p["w_in"]))) * jax.nn.silu(xn @ op(_f32(
        p["w_gate"])))
    m = op(hidden) @ op(_f32(p["w_out"]))
    return x + _rms_norm(m, p["ln2_post"], spec.norm_eps)


def hidden_states(params, ids, quantize=None, spec: Spec = OURO):
    """One sequence ``ids`` [T] -> the last pass's final-norm hidden
    states [T, E].

    ``quantize`` (a dtype) rounds every matmul operand to it first: the
    control that shows the tolerances would catch a lower precision."""
    def op(x):
        return _f32(x.astype(quantize)) if quantize is not None else x

    positions = jnp.arange(ids.shape[0])

    def one_pass(_, x):
        for p in params["layers"]:
            x = _layer(x, p, positions, op, spec)
        return _rms_norm(x, params["ln_f"], spec.norm_eps)

    x = _f32(jnp.take(params["embed"], ids, axis=0))
    return lax.fori_loop(0, spec.passes, one_pass, x)


def _logits(params, h, quantize):
    w = _f32(params["unembed"])
    if quantize is not None:
        h, w = _f32(h.astype(quantize)), _f32(w.astype(quantize))
    return jnp.einsum("te,ev->tv", h, w)


@functools.partial(jax.jit, static_argnames=("quantize", "spec"))
def mean_loss(params, ids, labels, quantize=None, spec: Spec = OURO):
    """Mean cross-entropy over ``ids``/``labels`` [B, T], one sequence
    and one block of positions at a time."""
    with jax.default_matmul_precision("highest"):
        def one(args):
            seq, lab = args
            h = hidden_states(params, seq, quantize, spec)
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


@functools.partial(jax.jit, static_argnames=("quantize", "spec"))
def logits_at(params, ids, positions, quantize=None, spec: Spec = OURO):
    """Logits [n, V] at ``positions`` [n] of one sequence ``ids`` [T],
    each conditioned on everything before it (teacher forcing)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, quantize, spec)
        return _logits(params, jnp.take(h, positions, axis=0), quantize)
