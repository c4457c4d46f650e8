"""The plain reference of Command A+'s block (``model_type:
cohere2_moe``), cut to one chip's share of a layer.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")``: no kernels, no cache, no batching across sequences, the
shared experts kept apart and averaged, the held routed experts as a
masked sum, and no import from ``dmlc_tpu.models`` or ``dmlc_tpu.ops``.
The whole sequence goes through every layer at once.  So that a
32k-token forward fits beside a serving engine it is computed in
blocks: attention a few query heads and ``Q_BLOCK`` queries at a time
(a sliding layer's block against the keys its window can reach, which
is only a cut of what the mask removes anyway), the FFN ``Q_BLOCK`` rows
at a time, each matrix cast to float32 where it is used.

The layer, on the residual stream x [T, E] (no biases anywhere):

  xn    = LayerNorm(x): (x - mean) / sqrt(var + NORM_EPS) * weight, mean
          and variance over the hidden axis.  ONE norm a layer: the
          block is parallel, x_out = x + Attn(xn) + FFN(xn)
  Attn  q = xn W_q -> H heads of d;  k = xn W_k, v = xn W_v -> H_kv
        heads of d; query head h reads K/V head h // (H / H_kv); scale
        d^-1/2; o = concat(heads) W_o.  Published layer j (layer i here
        is published ``layer_offset + i``) is FULL where (j + 1) %
        full_every == 0, else SLIDING.  A sliding layer rotates q and k
        by RoPE (theta ``rope_theta``) and query i sees keys i - window
        < j <= i.  A full layer applies NO positional embedding and is
        causal over the whole context
  FFN   s = sigmoid(xn W_r) in float32 over ALL routed experts; the
        ``top_k`` largest are the picks, w_i = s_i / (sum of the picked
        s + 1e-20) (norm_topk_prob; no scale factor, bias or groups);
        routed = sum over the picks HELD here of w_i E_i(xn), E(x) =
        W_down(silu(W_gate x) * W_up x); shared = the MEAN of the
        shared experts S_j(xn), each of the same shape
        (``shared_expert_combination_strategy: average``);
        FFN = routed + shared.  The normaliser is over all picks, held
        or not; what the absent experts would add is left out, as in
        the program
  head  logits = logit_scale * LayerNorm(x) E^T over the tied embedding

It reads the program's tree as stored (models/transformer.py,
``_init_held_expert_params``): ``embed [V, E]``, ``ln_f``, ``layers``
(a list, one dict a layer: ``ln1``, ``wq [E, H, d]``, ``wk`` / ``wv
[E, H_kv, d]``, ``wo [H, d, E]``, ``gate [E, routed]``, the shared
experts side by side in ``s_in`` / ``s_gate [E, n_shared x F]`` and
``s_out [n_shared x F, E]``) and ``experts`` (``w_in`` / ``w_gate
[L x X, E, F]``, ``w_out [L x X, F, E]``, layer i's held experts at
[i X, (i + 1) X)).  Every width and count comes from the tree's shapes.
What the tree does not carry is a constant below: the runner calls
``logits_at(params, ids, positions)`` with no configuration, and
tests/test_cohere2_family.py holds :data:`COMMAND_A_PLUS` to
``configs/command-a-plus-ep8-serve.json``.  A test at another size
passes its own :class:`Spec`.

Departures from the published description, each the program's own:
RoPE rotates the two halves of a head, not interleaved pairs
(``rope_gptj``), a relabelling of W_q's and W_k's columns that seeded
random weights cannot see; the vision tower is not loaded; the
``prefix_dense_*`` keys are unused (``first_k_dense_replace`` 0).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512
HEAD_BLOCK = 4


class Spec(NamedTuple):
    """What the parameter tree does not carry."""
    top_k: int = 8                # num_experts_per_tok
    held_start: int = 0           # first routed expert held here
    window: int = 4096            # sliding_window
    rope_theta: float = 50000.0
    full_every: int = 4           # layer_switch
    layer_offset: int = 0         # published index of layer 0
    norm_eps: float = 1e-5        # layer_norm_eps
    logit_scale: float = 1.0


COMMAND_A_PLUS = Spec()


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, weight, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * _f32(weight)


def _rope(x, positions, theta):
    """x [T, h, D] at positions [T], halves rotated."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = _f32(positions)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, window):
    """q [T, h, d] (h query heads that share ONE K/V head), k, v [T, d]
    -> [T, h, d]: causal, and inside ``window`` where it is not 0.  A
    block of queries at a time, against the whole context or, under a
    window, against the ``window + block`` keys that can reach it."""
    t, _, d = q.shape
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
        return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1), vs)

    return lax.map(block, jnp.arange(0, t, qb)).reshape(q.shape)


def _attention(xn, p, positions, sliding: bool, op, spec: Spec):
    """xn [T, E] (rounded by ``op``) -> attention's addend [T, E]."""
    n_heads, n_kv = p["wq"].shape[1], p["wk"].shape[1]
    group = n_heads // n_kv
    hb = min(HEAD_BLOCK, group)
    assert n_kv * group == n_heads and group % hb == 0, (n_heads, n_kv, hb)
    k = jnp.einsum("te,ehd->thd", xn, op(_f32(p["wk"])))
    v = jnp.einsum("te,ehd->thd", xn, op(_f32(p["wv"])))
    if sliding:
        k = _rope(k, positions, spec.rope_theta)
    k, v = op(k), op(v)

    def heads(y, first):
        kv = first // group  # the K/V head these query heads read
        q = jnp.einsum("te,ehd->thd", xn, op(_f32(
            lax.dynamic_slice_in_dim(p["wq"], first, hb, axis=1))))
        if sliding:
            q = _rope(q, positions, spec.rope_theta)
        o = _attend(op(q), lax.dynamic_index_in_dim(k, kv, 1, False),
                    lax.dynamic_index_in_dim(v, kv, 1, False),
                    spec.window if sliding else 0)
        w_o = op(_f32(lax.dynamic_slice_in_dim(p["wo"], first, hb, axis=0)))
        return y + jnp.einsum("thd,hde->te", op(o), w_o), None

    y, _ = lax.scan(heads, jnp.zeros_like(xn), jnp.arange(0, n_heads, hb))
    return y


def _swiglu(xn, w_in, w_gate, w_out, op):
    hidden = (xn @ op(_f32(w_in))) * jax.nn.silu(xn @ op(_f32(w_gate)))
    return op(hidden) @ op(_f32(w_out))


def _ffn(xn, p, experts, first, n_held, op, spec: Spec):
    """xn [T, E] unrounded -> the FFN's addend: the held routed
    experts' part and the shared experts' mean.  This layer's held
    experts are ``experts[name][first:first + n_held]`` of the stacks
    (one expert is cut out at a time: a layer's slice of a stack would
    be a copy of half a gigabyte a matrix).  ``Q_BLOCK`` rows at a
    time."""
    width = experts["w_in"].shape[-1]
    n_shared = p["s_in"].shape[-1] // width

    def rows(xn):
        # the router stays in float32 on unrounded operands, in the
        # control too, as the family's implementations keep it
        scores = jax.nn.sigmoid(xn @ _f32(p["gate"]))
        top_s, top_i = lax.top_k(scores, spec.top_k)
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

        def shared(y, j):
            at = j * width
            return y + _swiglu(
                xn, lax.dynamic_slice_in_dim(p["s_in"], at, width, 1),
                lax.dynamic_slice_in_dim(p["s_gate"], at, width, 1),
                lax.dynamic_slice_in_dim(p["s_out"], at, width, 0), op), None

        mean, _ = lax.scan(shared, jnp.zeros_like(xn), jnp.arange(n_shared))
        return y + mean / n_shared

    t, e = xn.shape
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    return lax.map(rows, xn.reshape(t // qb, qb, e)).reshape(t, e)


def hidden_states(params, ids, quantize=None, spec: Spec = COMMAND_A_PLUS):
    """One sequence ``ids`` [T] -> final-norm hidden states [T, E].

    ``quantize`` (a dtype) rounds every matmul operand but the router's
    to it first: the control that shows the tolerances would catch a
    lower precision."""
    def op(x):
        return _f32(x.astype(quantize)) if quantize is not None else x

    positions = jnp.arange(ids.shape[0])
    x = _f32(jnp.take(params["embed"], ids, axis=0))
    layers = params["layers"]
    n_held = params["experts"]["w_in"].shape[0] // len(layers)
    for i, p in enumerate(layers):
        sliding = (spec.layer_offset + i + 1) % spec.full_every != 0
        xn = _layer_norm(x, p["ln1"], spec.norm_eps)
        x = (x + _attention(op(xn), p, positions, sliding, op, spec)
             + _ffn(xn, p, params["experts"], i * n_held, n_held, op, spec))
    return _layer_norm(x, params["ln_f"], spec.norm_eps)


def _logits(params, h, quantize, spec: Spec):
    w = _f32(params["embed"])
    if quantize is not None:
        h, w = _f32(h.astype(quantize)), _f32(w.astype(quantize))
    return spec.logit_scale * jnp.einsum("te,ve->tv", h, w)


@functools.partial(jax.jit, static_argnames=("quantize", "spec"))
def mean_loss(params, ids, labels, quantize=None,
              spec: Spec = COMMAND_A_PLUS):
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
                logits = _logits(params, hs, quantize, spec)
                lse = jax.nn.logsumexp(logits, axis=-1)
                hit = jnp.take_along_axis(logits, ls[:, None], axis=-1)
                return jnp.sum(lse - hit[:, 0])

            return jnp.sum(lax.map(block, jnp.arange(0, seq.shape[0], qb)))

        total = jnp.sum(lax.map(one, (ids, labels)))
    return total / ids.size


@functools.partial(jax.jit, static_argnames=("quantize", "spec"))
def logits_at(params, ids, positions, quantize=None,
              spec: Spec = COMMAND_A_PLUS):
    """Logits [n, V] at ``positions`` [n] of one sequence ``ids`` [T],
    each conditioned on everything before it (teacher forcing)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, quantize, spec)
        return _logits(params, jnp.take(h, positions, axis=0), quantize,
                       spec)
