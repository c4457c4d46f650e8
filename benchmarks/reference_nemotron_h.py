"""The plain reference of a model under a layer pattern (Nemotron 3
Super's block: ``model_type: nemotron_h``), cut to one chip's share of
a layer.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")``: no kernels, no cache, no state slots, the state-space
recurrence token by token under ``lax.scan`` (NOT the chunked form the
program prefills with), the convolution as its explicit sum, experts as
a masked sum over the held ones, and no import from ``dmlc_tpu.models``
or ``dmlc_tpu.ops``.  The whole sequence goes through one layer after
another; a layer's weights are cast to float32 where they are used, a
routed expert's cut out of the one stack inside the step that needs it,
so that a 9k-token forward fits beside a serving engine.

Every layer is ONE thing under one norm, ``x <- x + f(RMSNorm(x; eps
1e-5))``, named by its letter of ``Spec.pattern``; a final RMSNorm and
an untied head.  On the residual stream x [T, E], xn = rms(x):

  M   [z | xBC | dt] = xn W_in  (d_inner + conv_dim + H columns, no bias)
      xBC_t <- silu(sum_j c_j xBC_{t-(W-1)+j} + b_conv): causal,
      depthwise, W = 4 taps, zeros before the sequence
      xBC -> x [H, P], B [G, N], C [G, N]; head h in group h // (H / G)
      dt = softplus(dt + dt_bias) a head;  a = exp(-dt exp(A_log))
      h_t = a_t h_{t-1} + dt_t x_t (x) B_t;  y_t = h_t C_t + D x_t
      y <- RMSNorm_group(y * silu(z)) over each group's d_inner / G
      channels (the gate BEFORE the norm), times its weight
      x += y W_out
  *   q = xn W_q (H_q x d), k, v = xn W_k, xn W_v (H_kv x d); causal
      softmax at scale d^-1/2, query head h on K/V head h // (H_q /
      H_kv); x += o W_o.  No bias, no positional rotation.
  E   s = sigmoid(xn W_g) in float32 over ALL routed experts; the TOP_K
      largest of s + b (b the correction bias; one group: no limit);
      w_i = ROUTED_SCALE x s_i / (sum of the picked s + 1e-20);
      u = xn W_down;  r = sum over the picks HELD here of
      w_i W2_i relu(W1_i u)^2;  x += r W_up + S2 relu(S1 xn)^2.
      What the absent experts would add is left out, as in the program.

It reads the program's tree as stored (models/transformer.py,
``_init_pattern_params``): ``embed``, ``unembed``, ``ln_f``; ``layers``,
one dict a layer; ``experts``, the held routed experts of all expert
layers as one stack.  Every width comes from the tree's shapes; what it
does not carry is a constant of :class:`Spec`, which
tests/test_nemotron_h_family.py holds to
``configs/nemotron3-super-ep4-serve.json``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512
RMS_EPS = 1e-5


class Spec(NamedTuple):
    """What the parameter tree does not carry."""
    pattern: str = "MEMEMEM*EME"  # hybrid_override_pattern, layers 0-10
    top_k: int = 22               # num_experts_per_tok
    routed_scale: float = 5.0     # routed_scaling_factor
    held_start: int = 0           # first routed expert held here
    n_groups: int = 8             # the Mamba-2 mixer's groups of heads


NEMOTRON3 = Spec()


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + RMS_EPS) * _f32(scale)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def recurrence(x, dt, rate, b, c, state_dtype=None):
    """The state-space recurrence itself, a token at a time: x [T, H,
    P], dt [T, H], rate [H] = exp(A_log), b and c [T, G, N] -> h_t C_t
    [T, H, P] (without the skip term).  ``state_dtype`` keeps the state
    in a lower type between tokens (the control that shows a float32
    state is needed)."""
    def keep(s):
        if state_dtype is None:
            return s
        # reduce_precision, not a cast there and back: the TPU's
        # compiler drops a float32 -> bfloat16 -> float32 round trip
        fi = jnp.finfo(state_dtype)
        return lax.reduce_precision(s, fi.nexp, fi.nmant)

    h, p = x.shape[1:]
    per = h // b.shape[1]

    def token(s, xs):                                  # s [H, P, N]
        x_t, dt_t, b_t, c_t = xs
        a_t = jnp.exp(-dt_t * rate)
        b_h, c_h = jnp.repeat(b_t, per, 0), jnp.repeat(c_t, per, 0)
        s = keep(a_t[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_h)

    _, y = lax.scan(token, jnp.zeros((h, p, b.shape[2]), jnp.float32),
                    (x, dt, b, c))
    return y


def _mamba(xn, p, op, spec: Spec, state_dtype=None):
    """xn [T, E] (normed) -> the mixer's addend [T, E]."""
    t = xn.shape[0]
    n_heads = p["dt_bias"].shape[0]
    inner = p["norm"].shape[0]
    width, conv_dim = p["conv"].shape
    g = spec.n_groups
    n = (conv_dim - inner) // (2 * g)
    xq = op(xn)
    w_in = p["in_proj"]
    z = xq @ op(_f32(w_in[:, :inner]))
    xbc = xq @ op(_f32(w_in[:, inner:inner + conv_dim]))
    dt = jax.nn.softplus(xq @ op(_f32(w_in[:, inner + conv_dim:]))
                         + p["dt_bias"])                         # [T, H]
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = _f32(p["conv"])
    y = jax.nn.silu(sum(conv[j] * padded[j:j + t] for j in range(width))
                    + _f32(p["conv_b"]))
    x = y[:, :inner].reshape(t, n_heads, inner // n_heads)
    b = y[:, inner:inner + g * n].reshape(t, g, n)
    c = y[:, inner + g * n:].reshape(t, g, n)
    o = recurrence(x, dt, jnp.exp(p["a_log"]), b, c, state_dtype) \
        + p["d"][:, None] * x
    o = o.reshape(t, inner) * jax.nn.silu(z)
    grouped = o.reshape(t, g, inner // g)
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    o = (grouped * lax.rsqrt(var + RMS_EPS)).reshape(t, inner) \
        * _f32(p["norm"])
    return op(o) @ op(_f32(p["out_proj"]))


def _attention(xn, p, op):
    """xn [T, E] (normed) -> attention's addend [T, E]: one K/V head and
    its group of query heads at a time, a block of queries at a time
    against the whole context."""
    t = xn.shape[0]
    n_heads, d = p["wq"].shape[1:]
    n_kv = p["wk"].shape[1]
    per = n_heads // n_kv
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    key_pos = jnp.arange(t)
    xq = op(xn)
    y = jnp.zeros_like(xn)
    for j in range(n_kv):
        heads = slice(j * per, (j + 1) * per)
        q = op(jnp.einsum("te,ehd->thd", xq, op(_f32(p["wq"][:, heads]))))
        k = op(xq @ op(_f32(p["wk"][:, j])))                     # [T, d]
        v = op(xq @ op(_f32(p["wv"][:, j])))

        def block(start, q=q, k=k, v=v):
            qs = lax.dynamic_slice_in_dim(q, start, qb, axis=0)
            s = jnp.einsum("qhd,kd->hqk", qs, k) * d ** -0.5
            visible = key_pos[None, :] <= (start + jnp.arange(qb))[:, None]
            s = jnp.where(visible[None], s, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1), v)

        o = lax.map(block, jnp.arange(0, t, qb)).reshape(t, per, d)
        y = y + jnp.einsum("thd,hde->te", op(o), op(_f32(p["wo"][heads])))
    return y


def route(scores, bias, spec: Spec):
    """scores [T, X] float32 -> ``(weights [T, k], experts [T, k])``: the
    picks are the largest biased scores, the weights come from the
    unbiased ones."""
    picks = jnp.argsort(-(scores + _f32(bias)), axis=-1)[:, :spec.top_k]
    s = jnp.take_along_axis(scores, picks, axis=-1)
    weights = spec.routed_scale * s / (
        jnp.sum(s, axis=-1, keepdims=True) + 1e-20)
    return weights, picks


def _experts(xn, p, experts, first, n_held, op, spec: Spec):
    """xn [T, E] (normed) -> the expert layer's addend: the held routed
    experts' part, summed in the latent and projected up once, and the
    shared expert.  ``experts`` is the stack of every expert layer's
    held experts, this layer's at [first, first + n_held).  The router
    stays in float32 on unrounded operands, in the control too."""
    scores = jax.nn.sigmoid(xn @ _f32(p["gate"]))
    weight, top_i = route(scores, p["gate_bias"], spec)
    xq = op(xn)
    u = op(xq @ op(_f32(p["w_down"])))                           # [T, latent]

    def one(r, j):
        w_in, w_out = (_f32(lax.dynamic_index_in_dim(
            experts[name], first + j, keepdims=False))
            for name in ("w_in", "w_out"))
        mine = jnp.sum(jnp.where(top_i == spec.held_start + j, weight, 0.0),
                       axis=-1)                                  # [T]
        return r + mine[:, None] * (op(_relu2(u @ op(w_in))) @ op(w_out)), \
            None

    r, _ = lax.scan(one, jnp.zeros_like(u), jnp.arange(n_held))
    return op(r) @ op(_f32(p["w_up"])) \
        + op(_relu2(xq @ op(_f32(p["s_in"])))) @ op(_f32(p["s_out"]))


def hidden_states(params, ids, quantize=None, spec: Spec = NEMOTRON3,
                  state_dtype=None):
    """One sequence ``ids`` [T] -> final-norm hidden states [T, E].

    ``quantize`` (a dtype) rounds every matmul operand but the router's
    to it first, ``state_dtype`` the recurrent state between tokens:
    the controls that show the tolerances would catch a lower
    precision."""
    def op(x):
        return _f32(x.astype(quantize)) if quantize is not None else x

    layers = params["layers"]
    assert len(layers) == len(spec.pattern), (len(layers), spec.pattern)
    n_held = params["experts"]["w_in"].shape[0] // max(
        spec.pattern.count("E"), 1)
    x = _f32(jnp.take(params["embed"], ids, axis=0))
    seen_e = 0
    for letter, p in zip(spec.pattern, layers):
        xn = _rms_norm(x, p["ln"])
        if letter == "M":
            x = x + _mamba(xn, p, op, spec, state_dtype)
        elif letter == "*":
            x = x + _attention(xn, p, op)
        else:
            x = x + _experts(xn, p, params["experts"], seen_e * n_held,
                             n_held, op, spec)
            seen_e += 1
    return _rms_norm(x, params["ln_f"])


def _logits(params, h, quantize=None):
    w = _f32(params["unembed"])
    if quantize is not None:
        h, w = _f32(h.astype(quantize)), _f32(w.astype(quantize))
    return jnp.einsum("te,ev->tv", h, w)


@functools.partial(jax.jit,
                   static_argnames=("quantize", "spec", "state_dtype"))
def mean_loss(params, ids, labels, quantize=None, spec: Spec = NEMOTRON3,
              state_dtype=None):
    """Mean cross-entropy over ``ids``/``labels`` [B, T], one sequence
    and one block of positions at a time."""
    with jax.default_matmul_precision("highest"):
        def one(args):
            seq, lab = args
            h = hidden_states(params, seq, quantize, spec, state_dtype)
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


@functools.partial(jax.jit,
                   static_argnames=("quantize", "spec", "state_dtype"))
def logits_at(params, ids, positions, quantize=None, spec: Spec = NEMOTRON3,
              state_dtype=None):
    """Logits [n, V] at ``positions`` [n] of one sequence ``ids`` [T],
    each conditioned on everything before it (teacher forcing)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, quantize, spec, state_dtype)
        return _logits(params, jnp.take(h, positions, axis=0), quantize)
