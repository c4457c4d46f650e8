"""The plain reference of the latent family (DeepSeek-V3's block as
A.X-K1 publishes it), cut to one chip's share of a layer.

Straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")``: no kernels, no cache, no absorption of the key
up-projection into the query, experts as a masked sum over the held
ones, and no import from ``dmlc_tpu.models`` or ``dmlc_tpu.ops``.  The
whole sequence goes through every layer at once; attention is computed
a block of heads and a block of queries at a time and the layers run
under ``lax.scan`` with each layer's weights cast to float32 inside the
step, so that a 16k-token forward fits beside a serving engine.

The layer (eps 1e-6, no biases), on the residual stream x [T, E]:

  MLA   c_q = rms(xn W_qa); q = c_q W_qb -> H x (nope | rope)
        [c_kv | k_pe] = xn W_kva; c_kv = rms(c_kv)
        q_pe, k_pe = rope(.)  (k_pe is one key for all heads)
        [k_nope | v] = c_kv W_kvb -> H x (nope | v);  k = [k_nope | k_pe]
        o = causal softmax(q . k x (nope + rope)^-0.5 x m^2) v;  x += o W_o
        m = 0.1 x mscale_all_dim x ln(factor) + 1 (yarn); RoPE takes
        yarn's blended frequencies and unscaled cos / sin, because
        mscale = mscale_all_dim
  dense x += W_out((xn W_in) * silu(xn W_gate))            (leading layers)
  MoE   s = sigmoid(xn W_g) in float32 over ALL routed experts; the
        TOP_K largest are the picks (``topk_method: "none"`` read as: no
        group limit and no correction bias);
        w_i = ROUTED_SCALE x s_i / (sum of the picked s + 1e-20)
        x += sum over the picks HELD here of w_i E_i(xn) + E_shared(xn)
        The normaliser is over all picks, held or not; what the absent
        experts would add is left out, as in the program.

It reads the program's tree as stored (models/transformer.py,
``_init_latent_params``): ``embed [V, E]``, ``unembed [E, V]``,
``ln_f``, a leading-dense group ``dense`` stacked ``[n_dense, ...]``
and the expert layers ``blocks`` stacked ``[S, L/S, ...]``.  Every
width comes from the tree's shapes.  What the tree does not carry is a
constant below, as ``ROPE_THETA`` is in reference.py: the runner calls
``logits_at(params, ids, positions)`` with no configuration, and
tests/test_latent_family.py holds :data:`AXK1` to
``configs/axk1-ep16-serve.json``.  A test at another size passes its
own :class:`Spec`.

Departure from the published block, the program's own: RoPE rotates
the two halves of the rope part, not interleaved pairs, a relabelling
of W_qb's and W_kva's columns that seeded random weights cannot see.
"""

from __future__ import annotations

import functools
import math
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
    rope_theta: float = 10000.0
    yarn_factor: float = 32.0
    yarn_original: int = 4096     # original_max_position_embeddings
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 1.0


AXK1 = Spec()


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + RMS_EPS) * _f32(scale)


def _inv_freq(dim: int, spec: Spec):
    """Yarn's blended RoPE frequencies [dim / 2]."""
    base = spec.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if spec.yarn_factor == 1.0:
        return jnp.asarray(base, jnp.float32)

    def dim_of(turns):  # the dimension that turns this often in the original context
        return dim * math.log(spec.yarn_original / (turns * 2 * math.pi)) / (
            2 * math.log(spec.rope_theta))

    low = max(math.floor(dim_of(spec.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(spec.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(base / spec.yarn_factor * ramp + base * (1 - ramp),
                       jnp.float32)


def _softmax_scale(qk_dim: int, spec: Spec) -> float:
    m = 1.0
    if spec.yarn_factor > 1.0:
        m = 0.1 * spec.yarn_mscale_all_dim * math.log(spec.yarn_factor) + 1.0
    return qk_dim ** -0.5 * m * m


def _rope(x, positions, spec: Spec):
    """x [T, ..., D] at positions [T], halves rotated."""
    half = x.shape[-1] // 2
    angles = _f32(positions)[:, None] * _inv_freq(x.shape[-1], spec)[None]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
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


def _mla(x, p, positions, op, spec: Spec):
    """x [T, E] -> attention's addend [T, E]; ``op`` rounds a matmul
    operand (the precision control) or is the identity."""
    rkv = p["kv_norm"].shape[-1]
    pe = p["w_kva"].shape[-1] - rkv
    n_heads, qk = p["w_qb"].shape[-2:]
    nope = qk - pe
    hb = min(HEAD_BLOCK, n_heads)
    assert n_heads % hb == 0, (n_heads, hb)
    scale = _softmax_scale(qk, spec)
    xn = op(_rms_norm(x, p["ln1"]))
    c_q = op(_rms_norm(xn @ op(_f32(p["w_qa"])), p["q_norm"]))
    kva = xn @ op(_f32(p["w_kva"]))
    c_kv = op(_rms_norm(kva[:, :rkv], p["kv_norm"]))
    k_pe = _rope(kva[:, rkv:], positions, spec)                  # [T, pe]

    def heads(y, first):
        def cut(w, axis):
            return op(_f32(lax.dynamic_slice_in_dim(w, first, hb, axis)))

        q = jnp.einsum("tr,rhd->thd", c_q, cut(p["w_qb"], 1))
        kv = jnp.einsum("tr,rhd->thd", c_kv, cut(p["w_kvb"], 1))
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], positions, spec)], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, None], (x.shape[0], hb, pe))], -1)
        o = _causal_attention(op(q), op(k), op(kv[..., nope:]), scale)
        return y + jnp.einsum("thd,hde->te", op(o), cut(p["wo"], 0)), None

    y, _ = lax.scan(heads, jnp.zeros_like(x), jnp.arange(0, n_heads, hb))
    return y


def _swiglu(xn, w_in, w_gate, w_out, op):
    hidden = (xn @ op(_f32(w_in))) * jax.nn.silu(xn @ op(_f32(w_gate)))
    return op(hidden) @ op(_f32(w_out))


def _experts(x, p, op, spec: Spec):
    """x [T, E] -> the expert layer's addend: the held routed experts'
    part and the shared expert."""
    xn = _rms_norm(x, p["ln2"])
    # the router stays in float32 on unrounded operands, in the control
    # too, as the family's implementations keep it
    scores = jax.nn.sigmoid(xn @ _f32(p["gate"]))
    top_s, top_i = lax.top_k(scores, spec.top_k)
    weight = spec.routed_scale * top_s / (
        jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    xn = op(xn)

    def one(y, args):
        j, w_in, w_gate, w_out = args
        mine = jnp.sum(jnp.where(top_i == spec.held_start + j, weight, 0.0),
                       axis=-1)                                  # [T]
        return y + mine[:, None] * _swiglu(xn, w_in, w_gate, w_out, op), None

    held = p["w_in"].shape[0]
    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (jnp.arange(held), p["w_in"], p["w_gate"], p["w_out"]))
    return y + _swiglu(xn, p["s_in"], p["s_gate"], p["s_out"], op)


def hidden_states(params, ids, quantize=None, spec: Spec = AXK1):
    """One sequence ``ids`` [T] -> final-norm hidden states [T, E].

    ``quantize`` (a dtype) rounds every matmul operand but the router's
    to it first: the control that shows the tolerances would catch a
    lower precision."""
    def op(x):
        return _f32(x.astype(quantize)) if quantize is not None else x

    positions = jnp.arange(ids.shape[0])
    x = _f32(jnp.take(params["embed"], ids, axis=0))

    def dense_layer(x, p):
        x = x + _mla(x, p, positions, op, spec)
        xn = op(_rms_norm(x, p["ln2"]))
        return x + _swiglu(xn, p["w_in"], p["w_gate"], p["w_out"], op), None

    def expert_layer(x, p):
        x = x + _mla(x, p, positions, op, spec)
        return x + _experts(x, p, op, spec), None

    x, _ = lax.scan(dense_layer, x, params["dense"])
    blocks = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                          params["blocks"])
    x, _ = lax.scan(expert_layer, x, blocks)
    return _rms_norm(x, params["ln_f"])


def _logits(params, h, quantize=None):
    w = _f32(params["unembed"])
    if quantize is not None:
        h, w = _f32(h.astype(quantize)), _f32(w.astype(quantize))
    return jnp.einsum("te,ev->tv", h, w)


@functools.partial(jax.jit, static_argnames=("quantize", "spec"))
def mean_loss(params, ids, labels, quantize=None, spec: Spec = AXK1):
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
def logits_at(params, ids, positions, quantize=None, spec: Spec = AXK1):
    """Logits [n, V] at ``positions`` [n] of one sequence ``ids`` [T],
    each conditioned on everything before it (teacher forcing)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, ids, quantize, spec)
        return _logits(params, jnp.take(h, positions, axis=0), quantize)
