"""Kimi Delta Attention: the gated delta rule with a per-channel decay.

Per head, a float32 state ``S`` in R^{d_k x d_v}; per token a query and
a key (L2-normalised, the query scaled), a value, a log decay ``g`` in
(lower bound, 0) per key channel and a write strength ``beta``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Two forms of the same recurrence, both on the TRANSPOSED state
``S^T [H, d_v, d_k]`` (the key channel on the lanes, so the decay and
the rank-one write broadcast a row and only the two contractions cross
lanes):

* :func:`kda_chunk_scan`, prefill: chunks of 64 tokens, inside a chunk
  the rule as triangular products (the WY form: ``(I + A) U = beta (V -
  (K . Gamma) S_0)``), across chunks the state carried by ``lax.scan``.
  Plain ``jnp``, float32 (the model puts it under the scope
  ``kda/chunk_scan``).
* :func:`kda_state_step`, decode: one token a row.  The Pallas kernel
  ``kda_state_step`` reads a live row's state through its slot, applies
  decay, correction and write, emits ``o`` and stores the state IN
  PLACE (the state array is aliased to the output); a dead row's slot
  is neither read nor written.  Its lax form is the parity reference
  and what the CPU runs (``ops/dispatch.py`` decides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
#: heads of one row's state a grid step of the decode kernel holds:
#: 8 x 128 x 128 float32 = 512 KB in and as much out, double-buffered
HEAD_BLOCK = 8
_HI = lax.Precision.HIGHEST


def kda_chunk_scan(q, k, v, g, beta, chunk: int = CHUNK):
    """The delta rule over one sequence from a zero state.

    q, k ``[T, H, d_k]``, v ``[T, H, d_v]``, g ``[T, H, d_k]`` (log
    decay, <= 0), beta ``[T, H]``, all float32.  A position with
    ``beta = 0`` and ``g = 0`` leaves the state as it was: what padding
    must look like.  ``T`` need not be a multiple of ``chunk``.
    Returns ``(o [T, H, d_v], S^T [H, d_v, d_k])``, the state after the
    last position.

    Inside a chunk every decay ratio ``exp(G_t - G_s)``, t >= s, is
    formed from the difference itself and never as a product of
    ``exp(G_t)`` and ``exp(-G_s)``: a channel at the lower bound loses
    e^-320 over 64 tokens, and its inverse is no float32.
    """
    t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    nc = (t + pad) // chunk

    def split(a):  # [T, H, D] -> [nc, C, H, D], padded with zeros
        a = jnp.pad(a.astype(jnp.float32), ((0, pad), (0, 0), (0, 0)))
        return a.reshape(nc, chunk, h, a.shape[-1])

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(lower, -1)
    eye = jnp.eye(chunk, dtype=jnp.float32)

    def one_chunk(s, xs):                              # s [H, d_k, d_v]
        # heads outermost inside the chunk: a 1 MB transpose here, where
        # one of the whole sequence would be a copy of it
        qc, kc, vc, gc, bc = (jnp.swapaxes(x, 0, 1) for x in xs)  # [H, C, .]
        cum = jnp.cumsum(gc, axis=1)                   # G_t, inclusive
        ratio = jnp.exp(jnp.where(
            lower[None, :, :, None],
            cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
        kk = jnp.sum(kc[:, :, None, :] * kc[:, None, :, :] * ratio, -1)
        qk = jnp.sum(qc[:, :, None, :] * kc[:, None, :, :] * ratio, -1)
        gamma = jnp.exp(cum)                           # decay since S_0
        rhs = bc * (vc - jnp.einsum("htc,hcv->htv", kc * gamma, s,
                                    precision=_HI))
        u = jax.scipy.linalg.solve_triangular(
            eye + jnp.where(strict, kk * bc, 0.0), rhs, lower=True,
            unit_diagonal=True)
        o = (jnp.einsum("htc,hcv->htv", qc * gamma, s, precision=_HI)
             + jnp.einsum("hts,hsv->htv", jnp.where(lower, qk, 0.0), u,
                          precision=_HI))
        to_end = jnp.exp(cum[:, -1:, :] - cum)         # <= 1
        s = gamma[:, -1, :, None] * s + jnp.einsum(
            "hsc,hsv->hcv", kc * to_end, u, precision=_HI)
        return s, jnp.swapaxes(o, 0, 1)

    s, o = lax.scan(
        one_chunk, jnp.zeros((h, dk, dv), jnp.float32),
        (split(q), split(k), split(v), split(g), split(beta[..., None])))
    return o.reshape(nc * chunk, h, dv)[:t], jnp.swapaxes(s, 1, 2)


# ---- decode: one token a row, the state updated in place --------------

def state_step_supports(n_heads: int, dk: int, dv: int) -> bool:
    """Whether the kernel serves these shapes: square states of whole
    128-lane tiles (a value becomes a column through the diagonal of a
    d_v x d_k tile) and whole blocks of heads."""
    return dk == dv and dk % 128 == 0 and n_heads % HEAD_BLOCK == 0


def _lax_state_step(q, k, v, g, kb, state, slots, live):
    """The kernel's lax form: gather, one step of the rule, scatter
    (a dead row's write is dropped, so its slot keeps its bits)."""
    s = state[slots] * jnp.exp(g)[:, :, None, :]       # [B, H, d_v, d_k]
    u = v - jnp.einsum("bhvk,bhk->bhv", s, k, precision=_HI)
    s = s + u[..., None] * kb[:, :, None, :]
    o = jnp.einsum("bhvk,bhk->bhv", s, q, precision=_HI)
    state = state.at[jnp.where(live, slots, state.shape[0])].set(
        s, mode="drop")
    return o, state


def _state_step_kernel(slot_ref, live_ref, q_ref, k_ref, v_ref, g_ref,
                       kb_ref, s_ref, o_ref, s_out_ref):
    from jax.experimental import pallas as pl

    hb, d = s_ref.shape[1], s_ref.shape[-1]
    n_live = live_ref[0]

    @pl.when(pl.program_id(0) < n_live)
    def _step():
        eye = (lax.broadcasted_iota(jnp.int32, (d, d), 0)
               == lax.broadcasted_iota(jnp.int32, (d, d), 1))
        for i in range(hb):
            row = lambda ref: ref[0, i:i + 1, :]       # noqa: E731 [1, d]
            s = s_ref[0, i] * jnp.exp(row(g_ref))      # [d_v, d_k]
            # u = v - S k as a column: v crosses over on the diagonal
            u = jnp.sum(jnp.where(eye, row(v_ref), 0.0) - s * row(k_ref),
                        axis=1, keepdims=True)
            s = s + u * row(kb_ref)
            s_out_ref[0, i] = s
            o = jnp.sum(s * row(q_ref), axis=1, keepdims=True)
            # and o comes back as a row the same way
            o_ref[0, i:i + 1, :] = jnp.sum(jnp.where(eye, o, 0.0), axis=0,
                                           keepdims=True)

    # no live row at all: the one block every step maps to goes back as
    # it came (the engine never asks for this; a caller may)
    @pl.when(n_live == 0)
    def _keep():
        s_out_ref[...] = s_ref[...]


def _pallas_state_step(q, k, v, g, kb, state, slots, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    hb = HEAD_BLOCK
    n_hb = h // hb
    # live rows first: a dead row's grid step maps to the block before
    # it, which Pallas neither fetches nor writes again
    order = jnp.argsort(~live, stable=True)
    n_live = jnp.sum(live.astype(jnp.int32)).reshape(1)
    rows = [a[order] for a in (q, k, v, g, kb)]

    def of_row(bi, j, slot_, live_):
        return (bi, j, 0)

    def of_slot(bi, j, slot_, live_):
        dead = bi >= live_[0]
        last = jnp.maximum(live_[0] - 1, 0)
        return (slot_[jnp.where(dead, last, bi)],
                jnp.where(dead, n_hb - 1, j), 0, 0)

    vec = pl.BlockSpec((1, hb, d), of_row)
    mat = pl.BlockSpec((1, hb, d, d), of_slot)
    o, state = pl.pallas_call(
        _state_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, n_hb),
            in_specs=[vec] * 5 + [mat], out_specs=[vec, mat]),
        out_shape=[jax.ShapeDtypeStruct((b, h, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two prefetched scalars: the state is 7th
        input_output_aliases={7: 1},
        name="kda_state_step",
        interpret=interpret,
    )(slots[order].astype(jnp.int32), n_live, *rows, state)
    return jnp.zeros_like(o).at[order].set(o), state


def kda_state_step(q, k, v, g, beta, state, slots, live, *,
                   impl: str = "auto", interpret: bool = False):
    """One token of the delta rule for a batch of decode rows.

    q, k, g ``[B, H, d_k]``, v ``[B, H, d_v]``, beta ``[B, H]``;
    ``state [n_slots, H, d_v, d_k]`` float32, every row's S^T by slot
    (the callers pass all layers' slots as ONE run, the layer's own
    offset added to ``slots``: a per-layer slice of the array would be
    copied for the kernel); ``slots [B]`` int32, ``live [B]`` bool.
    Returns ``(o [B, H, d_v] float32, state)``: the state of a live
    row's slot updated, every other slot bit for bit as it was.  The
    caller donates ``state``, which makes the update in place."""
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"unknown kda_state_step impl {impl!r}")
    from . import dispatch

    _, h, dk = q.shape
    mode = dispatch.choose(state_step_supports(h, dk, v.shape[-1]), impl)
    rows = [a.astype(jnp.float32) for a in (q, k, v, g, k * beta[..., None])]
    if mode != dispatch.LAX:
        return _pallas_state_step(*rows, state, slots, live,
                                  interpret or mode == dispatch.INTERPRET)
    return _lax_state_step(*rows, state, slots, live)
