"""Bytes a looped model's decode step reads (Ouro's block:
the same layers run ``loop_passes`` times a token, each pass with its
own cache layers), from shapes: what the ALGORITHM needs, whatever
implements it (costs.py's rule).  ``model`` is the ``model`` object of
a configuration file."""

from __future__ import annotations

from benchmarks import costs


def passes(model: dict) -> int:
    """How many times a token runs the layers: 1 for any other model."""
    return int(model.get("loop_passes", 1))


def cache_layers(model: dict) -> int:
    """K/V layers a token keeps: one a layer and pass, 192 for Ouro."""
    return passes(model) * model["n_layers"]


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one cached token in every cache layer: 192 x 2 x 16 x
    128 x 2 B = 1,572,864 B for Ouro-2.6B."""
    h_kv = model.get("n_kv_heads") or model["n_heads"]
    return (2 * cache_layers(model) * h_kv * model["head_dim"]
            * costs.dtype_bytes(model))


def layer_weight_bytes(model: dict) -> int:
    """One layer's weights: q and o at n_heads, k and v at the K/V
    heads, the dense SwiGLU's three matrices and four norm scales."""
    e, d = model["d_model"], model["head_dim"]
    h_kv = model.get("n_kv_heads") or model["n_heads"]
    params = (2 * e * model["n_heads"] * d + 2 * e * h_kv * d
              + 3 * e * model["d_ff"] + 4 * e)
    return params * costs.dtype_bytes(model)


def decode_weight_bytes(model: dict) -> int:
    """Weights a decode step has to read: every layer once a pass, the
    head once: 4 x 48 x 102.8 MB + 201 MB = 19.9 GB for Ouro-2.6B."""
    return (passes(model) * model["n_layers"] * layer_weight_bytes(model)
            + model["d_model"] * model["vocab"] * costs.dtype_bytes(model))

