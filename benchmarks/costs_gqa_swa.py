"""Operations and bytes of grouped-query attention with sliding-window
and full layers, from shapes: what the ALGORITHM needs, whatever
implements it (costs.py's rule).  ``model`` is the ``model`` object of
a configuration file."""

from __future__ import annotations

from benchmarks import costs


def layer_kinds(model: dict) -> list:
    """Each layer's attention: layer i is published layer layer_offset
    + i, "full" where (that + 1) is a multiple of layer_group_size, else
    "sliding"; none of either without a sliding window."""
    if not model.get("sliding_window"):
        return []
    group, first = model["layer_group_size"], model.get("layer_offset", 0)
    return ["full" if (first + i + 1) % group == 0 else "sliding"
            for i in range(model["n_layers"])]


def kv_heads(model: dict) -> int:
    return model.get("n_kv_heads") or model["n_heads"]


def visible_pairs(t: int, window: int) -> float:
    """(query, key) pairs a causal mask over ``t`` tokens keeps, query i
    seeing keys i - window < j <= i (``window`` 0: all before it)."""
    if not window or t <= window:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def gqa_prefill_attn_cost(model: dict, t: int, window: int) -> dict:
    """One prefill attention of one layer over one prompt of ``t``
    tokens, with a sliding window or (``window`` 0) without: per query
    head the score and the value product over the VISIBLE pairs only,
    2 x 2 x pairs x H x d FLOPs (a full layer at 32k: 128 heads x 537M
    pairs x 512 = 35.2 TFLOP; a sliding one 8.0); q read and o written
    at H heads, k and v read at the K/V heads, once each."""
    h, d = model["n_heads"], model["head_dim"]
    moved = t * d * (2 * h + 2 * kv_heads(model)) * costs.dtype_bytes(model)
    return {"per": "call", "flops": 4.0 * visible_pairs(t, window) * h * d,
            "bytes": float(moved)}


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token in one layer: 2 x H_kv x d values (2 x 8 x
    128 x 2 B = 4,096 B for Command A+)."""
    return 2 * kv_heads(model) * model["head_dim"] * costs.dtype_bytes(model)


def gqa_decode_attn_cost(model: dict, attended_keys: float) -> dict:
    """One decode attention of one layer at ``attended_keys`` keys over
    all live rows (a row of n tokens attends n in a full layer and at
    most the window in a sliding one): the K and V of those keys read
    once for all the query heads of their group, 4 x H x d FLOPs a key
    (16 FLOPs a byte at 128 heads on 8: far under the v5e's ridge of
    240, so the read bounds it)."""
    h, d = model["n_heads"], model["head_dim"]
    return {"per": "call", "flops": 4.0 * attended_keys * h * d,
            "bytes": float(attended_keys * kv_bytes_per_token(model))}
