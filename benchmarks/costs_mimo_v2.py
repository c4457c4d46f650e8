"""Operations and bytes of MiMo-V2's attention, from shapes: what the
ALGORITHM needs, whatever implements it (costs.py's rule).  Each kind
of layer has a geometry of its own: a full layer ``n_kv_heads`` K/V
heads, a sliding one ``swa_n_kv_heads`` and a window, keys ``head_dim``
wide and values ``v_head_dim``, and a sliding layer's learned sink adds
one exponential a row and query head.  ``model`` is the ``model``
object of a configuration file."""

from __future__ import annotations

from benchmarks import costs, costs_gqa_swa


def layer_kinds(model: dict) -> list:
    """Each layer's attention: layer i is published layer layer_offset
    + i, "full" where ``attn_pattern`` has "F" there and "sliding" where
    it has "S"; none without the pattern."""
    pattern = model.get("attn_pattern", "")
    first = model.get("layer_offset", 0)
    return [{"F": "full", "S": "sliding"}[c]
            for c in pattern[first:first + model["n_layers"]]]


def geometry(model: dict, kind: str) -> tuple:
    """``(K/V heads, key width, value width, window, sinks)`` of a layer
    of this kind."""
    sliding = kind == "sliding"
    h_kv = (model.get("swa_n_kv_heads") if sliding else 0) or \
        model.get("n_kv_heads") or model["n_heads"]
    d = model["head_dim"]
    return (h_kv, d, model.get("v_head_dim") or d,
            model["sliding_window"] if sliding else 0,
            bool(sliding and model.get("swa_sink")))


def prefill_attn_cost(model: dict, t: int, window: int) -> dict:
    """One prefill attention of one layer over one prompt of ``t``
    tokens, sliding (``window`` > 0) or full: per query head the score
    at the key width and the value product at the value width over the
    VISIBLE pairs only, 2 x pairs x H x (d_k + d_v) FLOPs, and a sliding
    layer's sink one more exponential a row and head (a full layer at
    16k: 64 heads x 134M pairs x 320 x 2 = 5.5 TFLOP; a sliding one
    0.17); q read at H heads of d_k, o written at H of d_v, k and v
    read once at the layer's K/V heads."""
    h = model["n_heads"]
    h_kv, dk, dv, _, sink = geometry(model, "sliding" if window else "full")
    pairs = costs_gqa_swa.visible_pairs(t, window)
    moved = t * (h * (dk + dv) + h_kv * (dk + dv)) * costs.dtype_bytes(model)
    return {"per": "call",
            "flops": 2.0 * pairs * h * (dk + dv) + (t * h if sink else 0.0),
            "bytes": float(moved)}


def kv_bytes_per_token(model: dict, kind: str) -> int:
    """K and V of one token in one layer of this kind: H_kv x (d_k +
    d_v) values (4 x 320 x 2 B = 2,560 B in a full layer, 5,120 B in a
    sliding one)."""
    h_kv, dk, dv, _, _ = geometry(model, kind)
    return h_kv * (dk + dv) * costs.dtype_bytes(model)


def decode_attn_cost(model: dict, kind: str, attended_keys: float,
                     rows: float) -> dict:
    """One decode attention of one layer of this kind at
    ``attended_keys`` keys over ``rows`` live rows (a row of n tokens
    attends n + 1 keys in a full layer and at most the window in a
    sliding one): the K and V of those keys read once for all the
    query heads of their group, 2 x H x (d_k + d_v) FLOPs a key, and a
    sliding layer's sink one exponential a row and head (64 x 320 x 2
    / 2,560 = 16 FLOPs a byte in a full layer, 8 in a sliding one: far
    under the v5e's ridge of 240, so the read bounds it)."""
    h = model["n_heads"]
    _, dk, dv, _, sink = geometry(model, kind)
    return {"per": "call",
            "flops": 2.0 * attended_keys * h * (dk + dv)
            + (rows * h if sink else 0.0),
            "bytes": float(attended_keys * kv_bytes_per_token(model, kind))}
