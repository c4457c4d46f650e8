"""Mamba-2: a selective state-space layer with a scalar decay a head.

Per head, a float32 state ``h`` in R^{P x N} (P the head's channels, N
the state size); per token the head's input ``x [P]``, a step ``dt > 0``
and a decay ``a = exp(-dt exp(A_log))`` in (0, 1], and per GROUP of
heads an input map ``B [N]`` and an output map ``C [N]``:

    h_t = a_t h_{t-1} + (dt_t x_t) (x) B_t
    y_t = h_t C_t                          (the caller adds D x_t)

No correction term and one decay a head: not the delta rule of
``ops/kda.py`` with other numbers.  Two forms of the recurrence, both on
the state ``[H, P, N]`` with the state size on the lanes:

* :func:`ssd_chunk_scan`, prefill: the state-space-duality form over
  chunks of 128 tokens.  Inside a chunk the output is a masked product
  ``(C B^T * L) (dt x)`` with ``L[t, s] = exp(sum of the log decays in
  (s, t])``; across chunks the state is carried by ``lax.scan``.  Plain
  ``jnp``, float32 (the model puts it under the scope
  ``mamba/chunk_scan``): NOT a Mosaic kernel.
* :func:`ssm_state_step`, decode: one token a row.  The Pallas kernel
  ``ssm_state_step`` reads a live row's state through its slot, decays
  it, adds the outer product, emits ``y`` and stores the state IN PLACE
  (the state array is aliased to the output); a dead row's slot is
  neither read nor written.  Its lax form is the parity reference and
  what the CPU runs (``ops/dispatch.py`` decides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 128
#: heads of one row's state a grid step of the decode kernel holds:
#: 16 x 64 x 128 float32 = 512 KB in and as much out, double-buffered;
#: the heads of ONE group, so a step reads one B and one C
HEAD_BLOCK = 16
LANES = 128
_HI = lax.Precision.HIGHEST


def ssd_chunk_scan(x, dt, rate, b, c, chunk: int = CHUNK, state=None):
    """The recurrence over one sequence, from ``state [H, P, N]`` or
    from a zero state.

    x ``[T, H, P]``, dt ``[T, H]`` (after softplus; 0 where the position
    is padding: it then neither decays nor writes the state), rate
    ``[H]`` = exp(A_log) > 0, b and c ``[T, G, N]`` with head ``h`` in
    group ``h // (H / G)``; all float32.  ``T`` need not be a multiple
    of ``chunk``.  Returns ``(y [T, H, P], h [H, P, N])``: the outputs
    without the skip term, and the state after the last position.

    Every decay ratio is ``exp`` of a difference of cumulated log
    decays, never a quotient of two ``exp``s."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    per = h // g
    pad = -t % chunk
    nc = (t + pad) // chunk

    def split(a):  # [T, ...] -> [nc, C, ...], padded with zeros
        a = jnp.pad(a.astype(jnp.float32),
                    ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((nc, chunk) + a.shape[1:])

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    rate = rate.astype(jnp.float32)

    def one_chunk(s, xs):                              # s [G, per, P, N]
        xc, dtc, bc, cc = xs
        cum = jnp.cumsum(-dtc * rate, axis=0).T        # [H, C], inclusive
        ratio = jnp.exp(jnp.where(lower, cum[:, :, None] - cum[:, None, :],
                                  -jnp.inf))           # [H, C(t), C(s)]
        cb = jnp.einsum("tgn,sgn->gts", cc, bc, precision=_HI)
        dtx = (xc * dtc[..., None]).reshape(chunk, g, per, p)
        inside = jnp.einsum(
            "ghts,sghp->tghp",
            cb[:, None] * ratio.reshape(g, per, chunk, chunk), dtx,
            precision=_HI)
        since = jnp.exp(cum).T.reshape(chunk, g, per)  # decay since s_0
        carried = since[..., None] * jnp.einsum(
            "tgn,ghpn->tghp", cc, s, precision=_HI)
        to_end = jnp.exp(cum[:, -1:] - cum).T.reshape(chunk, g, per)
        s = jnp.exp(cum[:, -1]).reshape(g, per, 1, 1) * s + jnp.einsum(
            "sghp,sgn->ghpn", dtx * to_end[..., None], bc, precision=_HI)
        return s, (inside + carried).reshape(chunk, h, p)

    s0 = jnp.zeros((g, per, p, n), jnp.float32) if state is None \
        else state.astype(jnp.float32).reshape(g, per, p, n)
    s, y = lax.scan(one_chunk, s0,
                    (split(x), split(dt), split(b), split(c)))
    return y.reshape(nc * chunk, h, p)[:t], s.reshape(h, p, n)


# ---- decode: one token a row, the state updated in place --------------

def state_step_supports(n_heads: int, n_groups: int, p: int, n: int) -> bool:
    """Whether the kernel serves these shapes: state rows of whole
    128-lane tiles, a head's channels within one (they cross from a row
    to a column on the diagonal of a ``[P, 128]`` tile), and groups of
    whole blocks of heads."""
    return (n % LANES == 0 and p % 8 == 0 and p <= LANES
            and n_heads % n_groups == 0
            and (n_heads // n_groups) % HEAD_BLOCK == 0)


def _lax_state_step(dtx, decay, b, c, state, slots, live):
    """The kernel's lax form: gather, one step, scatter (a dead row's
    write is dropped, so its slot keeps its bits)."""
    per = dtx.shape[1] // b.shape[1]
    b_h, c_h = (jnp.repeat(a, per, axis=1) for a in (b, c))   # [B, H, N]
    s = state[slots] * decay[:, :, None, None] \
        + dtx[..., None] * b_h[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s, c_h, precision=_HI)
    state = state.at[jnp.where(live, slots, state.shape[0])].set(
        s, mode="drop")
    return y, state


def _state_step_kernel(slot_ref, live_ref, x_ref, a_ref, b_ref, c_ref, s_ref,
                       o_ref, s_out_ref):
    from jax.experimental import pallas as pl

    hb, p = s_ref.shape[1], s_ref.shape[2]
    n_live = live_ref[0]

    @pl.when(pl.program_id(0) < n_live)
    def _step():
        eye = (lax.broadcasted_iota(jnp.int32, (p, LANES), 0)
               == lax.broadcasted_iota(jnp.int32, (p, LANES), 1))
        b_row, c_row = b_ref[0, 0], c_ref[0, 0]        # [1, N]
        for i in range(hb):
            # dt x crosses from a row to a column on the diagonal
            col = jnp.sum(jnp.where(eye, x_ref[0, i:i + 1, :], 0.0),
                          axis=1, keepdims=True)       # [P, 1]
            s = s_ref[0, i] * a_ref[0, i:i + 1, :] + col * b_row
            s_out_ref[0, i] = s
            y = jnp.sum(s * c_row, axis=1, keepdims=True)
            # and y comes back as a row the same way
            o_ref[0, i:i + 1, :] = jnp.sum(jnp.where(eye, y, 0.0), axis=0,
                                           keepdims=True)

    # no live row at all: the one block every step maps to goes back as
    # it came (the engine never asks for this; a caller may)
    @pl.when(n_live == 0)
    def _keep():
        s_out_ref[...] = s_ref[...]


def _pallas_state_step(dtx, decay, b, c, state, slots, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, h, p = dtx.shape
    g, n = b.shape[1:]
    hb = HEAD_BLOCK
    n_hb, per_group = h // hb, h // g // hb
    # live rows first: a dead row's grid step maps to the block before
    # it, which Pallas neither fetches nor writes again
    order = jnp.argsort(~live, stable=True)
    n_live = jnp.sum(live.astype(jnp.int32)).reshape(1)
    # a head's channels as a 128-lane row, its decay on every lane of
    # one, a group's B and C as [1, N] pages of their own
    x_rows = jnp.pad(dtx, ((0, 0), (0, 0), (0, LANES - p)))[order]
    a_rows = jnp.broadcast_to(decay[:, :, None], (rows, h, n))[order]
    b_rows, c_rows = (a[order].reshape(rows, g, 1, n) for a in (b, c))

    def of_row(bi, j, slot_, live_):
        return (bi, j, 0)

    def of_group(bi, j, slot_, live_):
        return (bi, j // per_group, 0, 0)

    def of_slot(bi, j, slot_, live_):
        dead = bi >= live_[0]
        last = jnp.maximum(live_[0] - 1, 0)
        return (slot_[jnp.where(dead, last, bi)],
                jnp.where(dead, n_hb - 1, j), 0, 0)

    mat = pl.BlockSpec((1, hb, p, n), of_slot)
    group = pl.BlockSpec((1, 1, 1, n), of_group)
    o, state = pl.pallas_call(
        _state_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, n_hb),
            in_specs=[pl.BlockSpec((1, hb, LANES), of_row),
                      pl.BlockSpec((1, hb, n), of_row), group, group, mat],
            out_specs=[pl.BlockSpec((1, hb, LANES), of_row), mat]),
        out_shape=[jax.ShapeDtypeStruct((rows, h, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two prefetched scalars: the state is 7th
        input_output_aliases={6: 1},
        name="ssm_state_step",
        interpret=interpret,
    )(slots[order].astype(jnp.int32), n_live, x_rows, a_rows, b_rows, c_rows,
      state)
    return jnp.zeros_like(o).at[order].set(o)[..., :p], state


def ssm_state_step(dtx, decay, b, c, state, slots, live, *,
                   impl: str = "auto", interpret: bool = False):
    """One token of the recurrence for a batch of decode rows.

    dtx ``[B, H, P]`` (``dt x``), decay ``[B, H]``, b and c
    ``[B, G, N]``; ``state [n_slots, H, P, N]`` float32, every row's
    state by slot (the callers pass all layers' slots as ONE run, the
    layer's own offset added to ``slots``: a per-layer slice of the
    array would be copied for the kernel); ``slots [B]`` int32, ``live
    [B]`` bool.  Returns ``(y [B, H, P] float32, state)``: y without the
    skip term, the state of a live row's slot updated, every other slot
    bit for bit as it was.  The caller donates ``state``, which makes
    the update in place."""
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"unknown ssm_state_step impl {impl!r}")
    from . import dispatch

    _, h, p = dtx.shape
    g, n = b.shape[1:]
    mode = dispatch.choose(state_step_supports(h, g, p, n), impl)
    rows = [a.astype(jnp.float32) for a in (dtx, decay, b, c)]
    if mode != dispatch.LAX:
        return _pallas_state_step(*rows, state, slots, live,
                                  interpret or mode == dispatch.INTERPRET)
    return _lax_state_step(*rows, state, slots, live)
