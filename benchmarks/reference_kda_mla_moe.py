"""The plain reference of the hybrid family (Ling-3.0-flash's block:
``model_type: bailing_hybrid``), cut to one chip's share of a layer.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")``: no kernels, no cache, no state slots, the delta rule as
its token-by-token recurrence under ``lax.scan`` (NOT the chunked form
the program prefills with), latent attention up-projected, experts as a
masked sum over the held ones, and no import from ``dmlc_tpu.models`` or
``dmlc_tpu.ops``.  The whole sequence goes through every layer at once;
the layers run under ``lax.scan`` with each layer's weights cut out of
their group and cast to float32 inside the step, so that a 9k-token
forward fits beside a serving engine.

Pre-norm residual blocks, RMSNorm eps 1e-6, no biases.  Layer i of the
tree is published layer ``LAYER_OFFSET + i``; published layer j is MLA
where ``(j + 1) % LAYER_GROUP_SIZE == 0``, else KDA.  On the residual
stream x [T, E], xn = rms(x):

  KDA   [q~ | k~ | v~] = xn W_qkv; each channel through a causal
        depthwise convolution over time, y_t = sum_j c_j x_{t-(W-1)+j}
        (W = 4, zeros before the sequence), then SiLU; per head (H
        heads, d_k = d_v = d) q and k L2-normalised, q scaled d^-1/2
        g_t = LOWER_BOUND x sigmoid(exp(A_log_h) x (xn W_a + dt_bias)),
        alpha_t = exp(g_t) per head and key channel; beta_t =
        sigmoid(xn W_beta) per head
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1}
              + beta_t k_t v_t^T,   o_t = S_t^T q_t,   S_0 = 0
        x += (rms_head(o_t) x sigmoid(xn W_og)_h) W_o
        no positional rotation
  MLA   q = xn W_q -> H x (nope | rope) directly (q_lora_rank null)
        [c_kv | k_pe] = xn W_kva; c_kv = rms(c_kv); q_pe, k_pe = rope(.)
        [k_nope | v] = c_kv W_kvb;  k = [k_nope | k_pe]
        o = causal softmax(q . k x (nope + rope)^-1/2) v;  x += o W_o
  dense x += W_out((xn W_in) * silu(xn W_gate))          (leading layers)
  MoE   s = sigmoid(xn W_g) in float32 over ALL routed experts;
        selection on s + b (b the correction bias): the experts lie in
        N_GROUP groups of neighbours, a group's score is the sum of its
        two largest s + b, the TOPK_GROUP best groups stay, the TOP_K
        largest s + b inside them are the picks;
        w_i = ROUTED_SCALE x s_i / (sum of the picked s + 1e-20), the
        UNBIASED scores; x += sum over the picks HELD here of
        w_i E_i(xn) + E_shared(xn).  What the absent experts would add
        is left out, as in the program.

It reads the program's tree as stored (models/transformer.py,
``_init_hybrid_params``): ``embed``, ``unembed``, ``ln_f``; ``kda`` and
``mla`` stacked by layer of their kind (ln1 and the attention);
``dense`` [n_dense, ...] and ``blocks`` [1, n_moe, ...] (ln2 and the
FFN).  Every width comes from the tree's shapes; what it does not carry
is a constant of :class:`Spec`, which tests/test_hybrid_family.py holds
to ``configs/ling3-flash-ep8-serve.json``.

Departure from the published block, the program's own: RoPE rotates the
two halves of the rope part, not interleaved pairs (a relabelling of
W_q's and W_kva's columns that seeded random weights cannot see).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Q_BLOCK = 512
HEAD_BLOCK = 8
RMS_EPS = 1e-6


class Spec(NamedTuple):
    """What the parameter tree does not carry."""
    top_k: int = 8                # num_experts_per_tok
    routed_scale: float = 2.5     # routed_scaling_factor
    held_start: int = 0           # first routed expert held here
    n_group: int = 8
    topk_group: int = 4
    rope_theta: float = 6000000.0
    layer_group_size: int = 6
    layer_offset: int = 1         # published index of the tree's layer 0
    kda_lower_bound: float = -5.0


LING3 = Spec()


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + RMS_EPS) * _f32(scale)


def _rope(x, positions, spec: Spec):
    """x [T, ..., D] at positions [T], halves rotated."""
    dim = x.shape[-1]
    inv_freq = jnp.asarray(spec.rope_theta ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim), jnp.float32)
    angles = _f32(positions)[:, None] * inv_freq[None]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2)
                            + (dim // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _causal_attention(q, k, v, scale):
    """q, k [T, h, qk], v [T, h, dv] -> [T, h, dv], a block of queries
    at a time against the whole context."""
    t = q.shape[0]
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    key_pos = jnp.arange(t)

    def block(start):
        qs = lax.dynamic_slice_in_dim(q, start, qb, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        visible = key_pos[None, :] <= (start + jnp.arange(qb))[:, None]
        s = jnp.where(visible[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return lax.map(block, jnp.arange(0, t, qb)).reshape(t, q.shape[1], -1)


def _mla(xn, p, positions, op, spec: Spec):
    """xn [T, E] (normed) -> attention's addend [T, E]."""
    rkv = p["kv_norm"].shape[-1]
    pe = p["w_kva"].shape[-1] - rkv
    n_heads, qk = p["w_q"].shape[-2:]
    nope = qk - pe
    hb = min(HEAD_BLOCK, n_heads)
    assert n_heads % hb == 0, (n_heads, hb)
    xn = op(xn)
    kva = xn @ op(_f32(p["w_kva"]))
    c_kv = op(_rms_norm(kva[:, :rkv], p["kv_norm"]))
    k_pe = _rope(kva[:, rkv:], positions, spec)                  # [T, pe]

    def heads(y, first):
        def cut(w, axis):
            return op(_f32(lax.dynamic_slice_in_dim(w, first, hb, axis)))

        q = jnp.einsum("te,ehd->thd", xn, cut(p["w_q"], 1))
        kv = jnp.einsum("tr,rhd->thd", c_kv, cut(p["w_kvb"], 1))
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], positions, spec)], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, None], (xn.shape[0], hb, pe))], -1)
        o = _causal_attention(op(q), op(k), op(kv[..., nope:]), qk ** -0.5)
        return y + jnp.einsum("thd,hde->te", op(o), cut(p["wo"], 0)), None

    y, _ = lax.scan(heads, jnp.zeros_like(xn), jnp.arange(0, n_heads, hb))
    return y


def delta_rule(q, k, v, alpha, beta, state_dtype=None):
    """The recurrence itself, a token at a time: q, k, alpha [T, H, d_k],
    v [T, H, d_v], beta [T, H] -> o [T, H, d_v].  ``state_dtype`` keeps
    the state in a lower type between tokens (the control that shows a
    float32 state is needed)."""
    def keep(s):
        if state_dtype is None:
            return s
        # reduce_precision, not a cast there and back: the TPU's
        # compiler drops a float32 -> bfloat16 -> float32 round trip
        # (excess precision is allowed), and the control read 0
        fi = jnp.finfo(state_dtype)
        return lax.reduce_precision(s, fi.nexp, fi.nmant)

    def token(s, x):                                   # s [H, d_k, d_v]
        q_t, k_t, v_t, a_t, b_t = x
        s = a_t[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, s))
        s = keep(s + k_t[:, :, None] * u[:, None, :])
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = lax.scan(token, jnp.zeros((h, dk, dv), jnp.float32),
                    (q, k, v, alpha, beta))
    return o


def _kda(xn, p, op, spec: Spec, state_dtype=None):
    """xn [T, E] (normed) -> attention's addend [T, E]."""
    t = xn.shape[0]
    n_heads, d = p["dt_bias"].shape
    width = p["conv"].shape[0]
    xq = op(xn)
    qkv = xq @ op(_f32(p["w_qkv"]))                              # [T, 3 H d]
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    conv = _f32(p["conv"])
    y = sum(conv[j] * padded[j:j + t] for j in range(width))
    q, k, v = jnp.moveaxis(jax.nn.silu(y).reshape(t, 3, n_heads, d), 1, 0)

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    a = (xq @ op(_f32(p["w_a"]))).reshape(t, n_heads, d)
    g = spec.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * (a + p["dt_bias"]))
    beta = jax.nn.sigmoid(xq @ op(_f32(p["w_beta"])))            # [T, H]
    gate = jax.nn.sigmoid(xq @ op(_f32(p["w_og"])))
    o = delta_rule(unit(q) * d ** -0.5, unit(k), v, jnp.exp(g), beta,
                   state_dtype)
    o = _rms_norm(o, p["o_norm"]) * gate[..., None]
    return jnp.einsum("thd,hde->te", op(o), op(_f32(p["wo"])))


def _swiglu(xn, w_in, w_gate, w_out, op):
    hidden = (xn @ op(_f32(w_in))) * jax.nn.silu(xn @ op(_f32(w_gate)))
    return op(hidden) @ op(_f32(w_out))


def route(scores, bias, spec: Spec):
    """scores [T, X] float32 -> ``(weights [T, k], experts [T, k])``:
    group-limited selection on the biased scores, weights from the
    unbiased ones."""
    t, x = scores.shape
    biased = scores + _f32(bias)
    groups = biased.reshape(t, spec.n_group, x // spec.n_group)
    two = jnp.sort(groups, axis=-1)[..., -2:]
    best = jnp.argsort(-jnp.sum(two, axis=-1), axis=-1)[:, :spec.topk_group]
    stays = jnp.zeros((t, spec.n_group), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    allowed = jnp.repeat(stays, x // spec.n_group, axis=1)
    picks = jnp.argsort(-jnp.where(allowed, biased, -jnp.inf),
                        axis=-1)[:, :spec.top_k]
    s = jnp.take_along_axis(scores, picks, axis=-1)
    weights = spec.routed_scale * s / (
        jnp.sum(s, axis=-1, keepdims=True) + 1e-20)
    return weights, picks


def _experts(xn, p, op, spec: Spec):
    """xn [T, E] (normed) -> the expert layer's addend: the held routed
    experts' part and the shared expert.  The router stays in float32
    on unrounded operands, in the control too."""
    scores = jax.nn.sigmoid(xn @ _f32(p["gate"]))
    weight, top_i = route(scores, p["gate_bias"], spec)
    xn = op(xn)

    def one(y, args):
        j, w_in, w_gate, w_out = args
        mine = jnp.sum(jnp.where(top_i == spec.held_start + j, weight, 0.0),
                       axis=-1)                                  # [T]
        return y + mine[:, None] * _swiglu(xn, w_in, w_gate, w_out, op), None

    held = p["w_in"].shape[0]
    y, _ = lax.scan(one, jnp.zeros_like(xn),
                    (jnp.arange(held), p["w_in"], p["w_gate"], p["w_out"]))
    return y + _swiglu(xn, p["s_in"], p["s_gate"], p["s_out"], op)


def _switch(flags, here, yes, no, x, i):
    """``yes(x, i)`` in the layers whose flag is set, ``no(x, i)`` in
    the others; a kind that no layer has is never traced (its group of
    weights is empty)."""
    if not any(flags):
        return no(x, i)
    if all(flags):
        return yes(x, i)
    return lax.cond(here, yes, no, x, i)


def layer_kinds(n_layers: int, spec: Spec) -> list:
    return ["mla" if (spec.layer_offset + i + 1) % spec.layer_group_size == 0
            else "kda" for i in range(n_layers)]


def hidden_states(params, ids, quantize=None, spec: Spec = LING3,
                  state_dtype=None):
    """One sequence ``ids`` [T] -> final-norm hidden states [T, E].

    ``quantize`` (a dtype) rounds every matmul operand but the router's
    to it first, ``state_dtype`` the recurrent state between tokens:
    the controls that show the tolerances would catch a lower
    precision."""
    def op(x):
        return _f32(x.astype(quantize)) if quantize is not None else x

    def layer_of(group, i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), group)

    positions = jnp.arange(ids.shape[0])
    x = _f32(jnp.take(params["embed"], ids, axis=0))
    n_dense = params["dense"]["ln2"].shape[0]
    blocks = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                          params["blocks"])
    kinds = layer_kinds(n_dense + blocks["ln2"].shape[0], spec)

    def kda(x, i):
        p = layer_of(params["kda"], i)
        return _kda(_rms_norm(x, p["ln1"]), p, op, spec, state_dtype)

    def mla(x, i):
        p = layer_of(params["mla"], i)
        return _mla(_rms_norm(x, p["ln1"]), p, positions, op, spec)

    def dense(x, i):
        f = layer_of(params["dense"], i)
        return _swiglu(op(_rms_norm(x, f["ln2"])), f["w_in"], f["w_gate"],
                       f["w_out"], op)

    def experts(x, i):
        f = layer_of(blocks, i)
        return _experts(_rms_norm(x, f["ln2"]), f, op, spec)

    # the layers run under one scan, each kind's body traced once and
    # a layer's weights cut out of their group inside the step
    is_mla = [kind == "mla" for kind in kinds]
    is_dense = [i < n_dense for i in range(len(kinds))]
    of_kind = [sum(k == kind for k in kinds[:i])
               for i, kind in enumerate(kinds)]
    of_ffn = [i if i < n_dense else i - n_dense for i in range(len(kinds))]

    def layer(x, sel):
        mla_here, dense_here, ai, fi = sel
        x = x + _switch(is_mla, mla_here, mla, kda, x, ai)
        return x + _switch(is_dense, dense_here, dense, experts, x, fi), None

    x, _ = lax.scan(layer, x, (
        jnp.asarray(is_mla), jnp.asarray(is_dense),
        jnp.asarray(of_kind, jnp.int32), jnp.asarray(of_ffn, jnp.int32)))
    return _rms_norm(x, params["ln_f"])


def _logits(params, h, quantize=None):
    w = _f32(params["unembed"])
    if quantize is not None:
        h, w = _f32(h.astype(quantize)), _f32(w.astype(quantize))
    return jnp.einsum("te,ev->tv", h, w)


@functools.partial(jax.jit,
                   static_argnames=("quantize", "spec", "state_dtype"))
def mean_loss(params, ids, labels, quantize=None, spec: Spec = LING3,
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
def logits_at(params, ids, positions, quantize=None, spec: Spec = LING3,
              state_dtype=None):
    """Logits [n, V] at ``positions`` [n] of one sequence ``ids`` [T],
    each conditioned on everything before it (teacher forcing)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, quantize, spec, state_dtype)
        return _logits(params, jnp.take(h, positions, axis=0), quantize)
