"""The plain reference of MiMo-V2's language-model block (``model_type:
mimo_v2``), cut to one chip's share of a layer.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")``: no kernels, no cache, no batching across sequences, the
held routed experts as a masked sum, and no import from
``dmlc_tpu.models`` or ``dmlc_tpu.ops``.  The whole sequence goes
through every layer at once.  So that a 17k-token forward fits beside
a serving engine it is computed in blocks: attention a few query heads
and ``Q_BLOCK`` queries at a time (a sliding layer's block against the
keys its window can reach, which is only a cut of what the mask removes
anyway), the FFN ``Q_BLOCK`` rows at a time, each matrix cast to
float32 where it is used.

The layer, on the residual stream x [T, E] (no biases anywhere):

  xn    = RMSNorm(x): x / sqrt(mean(x^2) + NORM_EPS) * weight; the block
          is sequential, h = x + Attn(xn), x' = h + FFN(RMSNorm(h))
  Attn  Published layer j (layer i here is published ``layer_offset +
        i``) is FULL where ``pattern[j]`` is "F", SLIDING where it is
        "S".  q = xn W_q -> H heads of d_k; k = xn W_k -> H_kv heads of
        d_k, v = xn W_v -> H_kv heads of d_v, H_kv the layer's own (4 in
        a full layer, 8 in a sliding one); query head h reads K/V head
        h // (H / H_kv).  RoPE turns the first ``rope_dim`` dims of q
        and k as two halves, at theta ``rope_theta`` in a full layer
        and ``swa_rope_theta`` in a sliding one; the scores are q.k /
        sqrt(d_k).  A full layer is causal over the whole context; in a
        sliding one query i sees keys i - window < j <= i, and head h's
        learned sink b_h joins the denominator: p_ij = exp(s_ij) /
        (exp(b_h) + sum_j' exp(s_ij')).  o = value_scale * sum_j p_ij
        v_j, concat(heads) W_o
  FFN   a layer with a router: s = sigmoid(xn W_r) in float32 over ALL
        routed experts; the picks are the ``top_k`` largest s + bias
        (the correction bias steers the choice, noaux_tc with one
        group), w_i = s_i / (sum of the picked s + 1e-20)
        (norm_topk_prob; routed scaling 1); FFN = sum over the picks
        HELD here of w_i E_i(xn), E(x) = W_down(silu(W_gate x) * W_up
        x).  The normaliser is over all picks, held or not; what the
        absent experts would add is left out, as in the program.  A
        layer without one (layer 0) is one dense SwiGLU of d_ff
  head  logits = RMSNorm(x) W_unembed (untied)

It reads the program's tree as stored (models/transformer.py,
``_init_held_expert_params``): ``embed [V, E]``, ``unembed [E, V]``,
``ln_f``, ``layers`` (a list, one dict a layer: ``ln1``, ``ln2``,
``wq [E, H, d_k]``, ``wk [E, H_kv, d_k]``, ``wv [E, H_kv, d_v]``, ``wo
[H, d_v, E]``; a sliding layer's ``sink [H]``; the dense layer's
``w_in`` / ``w_gate [E, d_ff]``, ``w_out [d_ff, E]``, an expert layer's
``gate [E, routed]`` and ``gate_bias [routed]``) and ``experts``
(``w_in`` / ``w_gate [L_moe x X, E, F]``, ``w_out [L_moe x X, F, E]``,
the j-th expert layer's held experts at [j X, (j + 1) X)).  Every width
and count comes from the tree's shapes.  What the tree does not carry
is a constant below: the runner calls ``logits_at(params, ids,
positions)`` with no configuration, and tests/test_mimo_v2_family.py
holds :data:`MIMO_V2_5` to ``configs/mimo-v2.5-ep16-serve.json``.  A
test at another size passes its own :class:`Spec`.

Departures from the published description, each the program's own: the
fused QKV projection is three matrices (a relabelling that seeded
weights cannot see); RoPE turns two halves of the rotated dims, not
interleaved pairs; the vision and audio towers and the MTP layers are
not loaded; ``attention_chunk_size`` is unused (the window is 128).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512
HEAD_BLOCK = 4

#: the published hybrid_layer_pattern, "F" where it holds 0 (full
#: attention) and "S" where it holds 1 (sliding window)
PUBLISHED_PATTERN = "FSSSSF" + "SSSSSF" * 7


class Spec(NamedTuple):
    """What the parameter tree does not carry."""
    top_k: int = 8                # num_experts_per_tok
    held_start: int = 0           # first routed expert held here
    window: int = 128             # sliding_window
    rope_theta: float = 10000000.0
    swa_rope_theta: float = 10000.0
    rope_dim: int = 64            # partial_rotary_factor 0.334 x 192
    pattern: str = PUBLISHED_PATTERN
    layer_offset: int = 0         # published index of layer 0
    norm_eps: float = 1e-5        # layernorm_epsilon
    value_scale: float = 0.707    # attention_value_scale


MIMO_V2_5 = Spec()


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * _f32(weight)


def _rope(x, positions, theta, n):
    """x [T, h, D] at positions [T]: the first ``n`` dims turned as two
    halves, the rest as they are."""
    half = n // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = _f32(positions)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:n]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., n:]], -1)


def _attend(q, k, v, window, sink):
    """q [T, h, d_k] (h query heads that share ONE K/V head), k [T,
    d_k], v [T, d_v], ``sink`` [h] or None -> [T, h, d_v]: causal, and
    inside ``window`` where it is not 0.  A block of queries at a time,
    against the whole context or, under a window, against the ``window
    + block`` keys that can reach it."""
    t, h, d = q.shape
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    n_keys = min(t, window + qb) if window else t

    def block(start):
        qs = lax.dynamic_slice_in_dim(q, start, qb, axis=0)
        first = jnp.clip(start + qb - n_keys, 0, t - n_keys)
        ks = lax.dynamic_slice_in_dim(k, first, n_keys, axis=0)
        vs = lax.dynamic_slice_in_dim(v, first, n_keys, axis=0)
        s = jnp.einsum("qhd,kd->hqk", qs, ks) * (d ** -0.5)
        i = (start + jnp.arange(qb))[:, None]
        j = (first + jnp.arange(n_keys))[None, :]
        visible = j <= i
        if window:
            visible = visible & (i - j < window)
        s = jnp.where(visible[None], s, -jnp.inf)
        if sink is not None:
            # the sink is one more logit of the row, with no value
            s = jnp.concatenate(
                [s, jnp.broadcast_to(sink[:, None, None], (h, qb, 1))], -1)
        p = jax.nn.softmax(s, axis=-1)[..., :n_keys]
        return jnp.einsum("hqk,kd->qhd", p, vs)

    out = lax.map(block, jnp.arange(0, t, qb))
    return out.reshape(t, h, v.shape[-1])


def _attention(xn, p, positions, sliding: bool, op, spec: Spec):
    """xn [T, E] (rounded by ``op``) -> attention's addend [T, E]."""
    n_heads, n_kv = p["wq"].shape[1], p["wk"].shape[1]
    group = n_heads // n_kv
    hb = min(HEAD_BLOCK, group)
    assert n_kv * group == n_heads and group % hb == 0, (n_heads, n_kv, hb)
    theta = spec.swa_rope_theta if sliding else spec.rope_theta
    k = jnp.einsum("te,ehd->thd", xn, op(_f32(p["wk"])))
    v = jnp.einsum("te,ehd->thd", xn, op(_f32(p["wv"])))
    k, v = op(_rope(k, positions, theta, spec.rope_dim)), op(v)
    sink = _f32(p["sink"]) if sliding and "sink" in p else None

    def heads(y, first):
        kv = first // group  # the K/V head these query heads read
        q = jnp.einsum("te,ehd->thd", xn, op(_f32(
            lax.dynamic_slice_in_dim(p["wq"], first, hb, axis=1))))
        q = _rope(q, positions, theta, spec.rope_dim)
        o = _attend(op(q), lax.dynamic_index_in_dim(k, kv, 1, False),
                    lax.dynamic_index_in_dim(v, kv, 1, False),
                    spec.window if sliding else 0,
                    None if sink is None
                    else lax.dynamic_slice_in_dim(sink, first, hb))
        w_o = op(_f32(lax.dynamic_slice_in_dim(p["wo"], first, hb, axis=0)))
        return y + jnp.einsum("thd,hde->te", op(spec.value_scale * o),
                              w_o), None

    y, _ = lax.scan(heads, jnp.zeros_like(xn), jnp.arange(0, n_heads, hb))
    return y


def _swiglu(xn, w_in, w_gate, w_out, op):
    hidden = (xn @ op(_f32(w_in))) * jax.nn.silu(xn @ op(_f32(w_gate)))
    return op(hidden) @ op(_f32(w_out))


def _ffn(xn, p, experts, first, n_held, op, spec: Spec):
    """xn [T, E] unrounded -> the FFN's addend: the held routed
    experts' part, or the dense SwiGLU of a layer without a router.
    This layer's held experts are ``experts[name][first:first +
    n_held]`` of the stacks (one expert is cut out at a time).
    ``Q_BLOCK`` rows at a time."""
    def rows(xn):
        if "gate" not in p:
            return _swiglu(op(xn), p["w_in"], p["w_gate"], p["w_out"], op)
        # the router stays in float32 on unrounded operands, in the
        # control too, as the family's implementations keep it
        scores = jax.nn.sigmoid(xn @ _f32(p["gate"]))
        top_i = lax.top_k(scores + _f32(p["gate_bias"]), spec.top_k)[1]
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
        weight = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
        xn = op(xn)

        def routed(y, j):
            mine = jnp.sum(jnp.where(top_i == spec.held_start + j, weight,
                                     0.0), axis=-1)
            w_in, w_gate, w_out = (
                lax.dynamic_index_in_dim(experts[name], first + j, 0, False)
                for name in ("w_in", "w_gate", "w_out"))
            return y + mine[:, None] * _swiglu(xn, w_in, w_gate, w_out,
                                               op), None

        y, _ = lax.scan(routed, jnp.zeros_like(xn), jnp.arange(n_held))
        return y

    t, e = xn.shape
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    return lax.map(rows, xn.reshape(t // qb, qb, e)).reshape(t, e)


def hidden_states(params, ids, quantize=None, spec: Spec = MIMO_V2_5):
    """One sequence ``ids`` [T] -> final-norm hidden states [T, E].

    ``quantize`` (a dtype) rounds every matmul operand but the router's
    to it first: the control that shows the tolerances would catch a
    lower precision."""
    def op(x):
        return _f32(x.astype(quantize)) if quantize is not None else x

    positions = jnp.arange(ids.shape[0])
    x = _f32(jnp.take(params["embed"], ids, axis=0))
    layers = params["layers"]
    n_held = params["experts"]["w_in"].shape[0] // sum(
        "gate" in p for p in layers)
    held = 0
    for i, p in enumerate(layers):
        sliding = spec.pattern[spec.layer_offset + i] == "S"
        xn = _rms_norm(x, p["ln1"], spec.norm_eps)
        x = x + _attention(op(xn), p, positions, sliding, op, spec)
        xn = _rms_norm(x, p["ln2"], spec.norm_eps)
        x = x + _ffn(xn, p, params["experts"], held * n_held, n_held, op,
                     spec)
        held += "gate" in p
    return _rms_norm(x, params["ln_f"], spec.norm_eps)


def _logits(params, h, quantize):
    w = _f32(params["unembed"])
    if quantize is not None:
        h, w = _f32(h.astype(quantize)), _f32(w.astype(quantize))
    return h @ w


@functools.partial(jax.jit, static_argnames=("quantize", "spec"))
def mean_loss(params, ids, labels, quantize=None, spec: Spec = MIMO_V2_5):
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
def logits_at(params, ids, positions, quantize=None, spec: Spec = MIMO_V2_5):
    """Logits [n, V] at ``positions`` [n] of one sequence ``ids`` [T],
    each conditioned on everything before it (teacher forcing)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, quantize, spec)
        return _logits(params, jnp.take(h, positions, axis=0), quantize)
